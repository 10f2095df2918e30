"""Fabric front-end entrypoint: HTTP/SSE server over worker processes.

Deploys the router/replica fabric across processes (docs/SERVING.md
"Deploying as a service"): connects one ``RemoteReplica`` per worker
(scripts/serve_worker.py), runs the UNCHANGED ``RequestRouter``
placement/failover/migration loop behind an asyncio HTTP front end,
and drives the heartbeat monitor that turns a dead worker into a
wire-level failover replay:

  POST /v1/generate      -> SSE token stream
  GET  /healthz          -> fabric + heartbeat health (503 until a
                            replica accepts work)
  POST /drain/<replica>  -> graceful retire (queued work requeues)
  GET  /metrics-summary  -> per-replica engine summaries
  GET  /metrics          -> the whole fabric as one Prometheus scrape
                            target (text format 0.0.4)

Two ways to get workers:

  --workers host:port,host:port   connect to already-running workers
  --spawn N                       spawn N loopback workers here (one
                                  subprocess each; CI/smoke mode)

Prints one READY line once serving:

  SERVE_FABRIC_READY port=8100 workers=2 pid=12345

SIGTERM/SIGINT runs the rolling shutdown: drain every replica
(queued-but-unplaced work requeues while survivors exist), wait for
in-flight streams to finish, then — spawn mode — shut the workers
down.  ``--jsonl`` collects the fabric's serving_health records
(scripts/obs_report.py renders the fabric-health table); ``--spans``
writes the router's span stream (merge with the workers' via
scripts/trace_export.py for one cross-process timeline).
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def spawn_worker(config_path: str, replica_id: int, role: str, *,
                 capacity: int, tokens_per_tick: int, param_seed: int,
                 jsonl: str | None = None, spans: str | None = None,
                 adapters: list[str] | None = None,
                 obs_ring: int = 0,
                 extra_args: list[str] | None = None,
                 timeout_s: float = 120.0) -> tuple[subprocess.Popen, int]:
    """Spawn one serve_worker.py subprocess; returns (proc, port) once
    its READY line arrives.  Shared by this CLI and the tests.
    ``obs_ring`` sizes the worker's
    in-memory span ring (the wire-v5 obs_pull source); ``extra_args``
    passes any further serve_worker flags verbatim."""
    cmd = [sys.executable,
           os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "serve_worker.py"),
           "--config", config_path, "--replica-id", str(replica_id),
           "--role", role, "--capacity", str(capacity),
           "--tokens-per-tick", str(tokens_per_tick),
           "--param-seed", str(param_seed), "--port", "0"]
    if jsonl:
        cmd += ["--jsonl", jsonl]
    if spans:
        cmd += ["--spans", spans]
    if obs_ring:
        cmd += ["--obs-ring", str(obs_ring)]
    for spec in adapters or []:
        cmd += ["--adapter", spec]
    cmd += extra_args or []
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)

    # the READY wait must honor timeout_s even when the worker wedges
    # WITHOUT writing a line (a blocking `for line in stdout` would
    # hang forever), so a reader thread feeds a queue we wait on with
    # a real deadline; the same thread then keeps draining the pipe so
    # the worker can never block on stdout
    import queue as _queue

    lines: _queue.Queue = _queue.Queue()

    def _pump():
        for line in proc.stdout:
            lines.put(line)
        lines.put(None)  # EOF (worker exited)

    threading.Thread(target=_pump, daemon=True).start()
    deadline = time.monotonic() + timeout_s
    port = None
    while port is None:
        try:
            line = lines.get(timeout=max(0.0, deadline - time.monotonic()))
        except _queue.Empty:
            break
        if line is None:
            break
        if line.startswith("SERVE_WORKER_READY"):
            port = int(dict(kv.split("=") for kv in line.split()[1:])["port"])
    if port is None:
        proc.kill()
        raise RuntimeError(
            f"worker {replica_id} never printed its READY line within "
            f"{timeout_s}s (rc={proc.poll()})"
        )
    return proc, port


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", required=True, metavar="PATH",
                    help="ModelConfig JSON shared with the workers "
                         "(worker.config_to_json)")
    grp = ap.add_mutually_exclusive_group(required=True)
    grp.add_argument("--workers", metavar="HOST:PORT,...",
                     help="connect to already-running workers")
    grp.add_argument("--spawn", type=int, metavar="N",
                     help="spawn N loopback workers as subprocesses")
    ap.add_argument("--roles", default=None, metavar="R0,R1,...",
                    help="per-replica tier roles (mixed|prefill|decode; "
                         "default all mixed)")
    ap.add_argument("--http-host", default="127.0.0.1")
    ap.add_argument("--http-port", type=int, default=8100,
                    help="HTTP/SSE listen port (0 = ephemeral; see "
                         "READY line)")
    ap.add_argument("--heartbeat-ms", type=float, default=200.0)
    ap.add_argument("--miss-threshold", type=int, default=3)
    ap.add_argument("--capacity", type=int, default=4,
                    help="per-worker slot capacity (spawn mode)")
    ap.add_argument("--tokens-per-tick", type=int, default=8)
    ap.add_argument("--param-seed", type=int, default=0)
    ap.add_argument("--adapter", action="append", default=[],
                    metavar="NAME=PATH",
                    help="LoRA adapter factors (serving.adapters."
                         "save_adapter_file npz); repeatable.  Spawned "
                         "workers preload them; externally-started "
                         "workers get them pushed over the wire "
                         "(load_adapter RPC) at first use")
    ap.add_argument("--jsonl", default=None, metavar="PATH",
                    help="fabric serving_health record stream")
    ap.add_argument("--spans", default=None, metavar="PATH",
                    help="router span stream (trace_export.py input)")
    ap.add_argument("--obs-stream", default=None, metavar="PATH",
                    help="merged fabric obs stream: the controller "
                         "drains every worker's in-memory span ring "
                         "(wire-v5 obs_pull) into ONE jsonl here, each "
                         "record stamped obs_src=replicaN — "
                         "trace_export.py/obs_report.py input for a "
                         "live multi-host fabric with zero remote file "
                         "access")
    ap.add_argument("--obs-pull-s", type=float, default=0.5, metavar="S",
                    help="obs-ring drain interval (with --obs-stream)")
    ap.add_argument("--obs-ring", type=int, default=4096, metavar="N",
                    help="span-ring length passed to SPAWNED workers "
                         "when --obs-stream is set (externally-started "
                         "workers set their own --obs-ring)")
    ap.add_argument("--queue-cap", type=int, default=None, metavar="N",
                    help="admission control: shed new requests (HTTP "
                         "429 + Retry-After) once the fabric holds N "
                         "queued-but-unstarted requests (default: "
                         "cfg.admission_queue_cap; 0 = no cap)")
    ap.add_argument("--queue-deadline-ms", type=float, default=None,
                    metavar="MS",
                    help="admission control: default per-request queue "
                         "deadline — requests whose estimated wait "
                         "exceeds it are shed (default: "
                         "cfg.admission_deadline_ms; 0 = none; "
                         "requests may carry their own "
                         "queue_deadline_ms)")
    ap.add_argument("--autoscale-max", type=int, default=None,
                    metavar="N",
                    help="elastic fabric: let the AutoscaleController "
                         "grow each tier up to N workers (spawn mode "
                         "only — new replicas are spawned like the "
                         "seed ones; default: "
                         "cfg.autoscale_max_replicas; 0 = fixed fleet)")
    ap.add_argument("--state-dir", default=None, metavar="DIR",
                    help="durable session store for the fabric "
                         "(docs/SERVING.md 'Durable sessions'): "
                         "POST /v1/park serializes streams here and "
                         "POST /v1/resume {'session': id} re-admits "
                         "them on any worker; sessions survive front-"
                         "end restarts.  TTL/budget come from "
                         "cfg.session_ttl_s and cfg.session_host_bytes")
    args = ap.parse_args()

    from mamba_distributed_tpu.obs import (
        NULL_TRACER,
        SpanTracer,
        append_jsonl,
    )
    from mamba_distributed_tpu.serving import RequestRouter
    from mamba_distributed_tpu.serving.service.health import HeartbeatMonitor
    from mamba_distributed_tpu.serving.service.remote import RemoteReplica
    from mamba_distributed_tpu.serving.service.server import (
        FabricController,
        FabricHTTPServer,
    )
    from mamba_distributed_tpu.serving.service.worker import config_from_json

    cfg = config_from_json(args.config)
    autoscale_max = (args.autoscale_max if args.autoscale_max is not None
                     else cfg.autoscale_max_replicas)
    most_workers = max(args.spawn or 0, autoscale_max if args.spawn else 0)
    if most_workers > 1 and os.environ.get("JAX_PLATFORMS") != "cpu":
        # every worker process takes every local chip and nothing
        # assigns one to each, so a second worker on an accelerator
        # host can never come up.  This process stays off JAX and
        # cannot ask which backend the workers will get; the variable
        # is the one thing it can see.
        ap.error("more than one spawned worker needs JAX_PLATFORMS=cpu "
                 "(the loopback CI fabric): on an accelerator host one "
                 "worker process owns every local chip — use --spawn 1, "
                 "or start one worker per host and pass --workers")
    procs: list[subprocess.Popen] = []
    if args.spawn:
        n = args.spawn
    else:
        addrs = [a.strip() for a in args.workers.split(",") if a.strip()]
        n = len(addrs)
    roles = (args.roles.split(",") if args.roles else ["mixed"] * n)
    if len(roles) != n:
        ap.error(f"--roles names {len(roles)} role(s) for {n} worker(s)")

    if args.spawn:
        addrs = []
        for i in range(n):
            proc, port = spawn_worker(
                args.config, i, roles[i], capacity=args.capacity,
                tokens_per_tick=args.tokens_per_tick,
                param_seed=args.param_seed, adapters=args.adapter,
                obs_ring=(args.obs_ring if args.obs_stream else 0),
            )
            procs.append(proc)
            addrs.append(f"127.0.0.1:{port}")
    replicas = []
    for i, addr in enumerate(addrs):
        host, _, port = addr.rpartition(":")
        replicas.append(RemoteReplica(i, (host, int(port)), role=roles[i]))

    tracer = SpanTracer(args.spans) if args.spans else NULL_TRACER
    if args.jsonl:
        open(args.jsonl, "w").close()
        emit = lambda rec: append_jsonl(args.jsonl, rec)  # noqa: E731
    else:
        emit = None
    adapter_store = {}
    if args.adapter:
        from mamba_distributed_tpu.serving.adapters import load_adapter_file

        for spec in args.adapter:
            name, _, path = spec.partition("=")
            if not name or not path:
                ap.error(f"--adapter expects NAME=PATH, got {spec!r}")
            adapter_store[name] = {"factors": load_adapter_file(path),
                                   "alpha": None}
    session_store = None
    if args.state_dir:
        from mamba_distributed_tpu.serving.sessions import (
            DiskSessionStore,
            SessionStore,
        )

        session_store = SessionStore(
            ttl_s=float(cfg.session_ttl_s),
            host_bytes=int(cfg.session_host_bytes),
            disk=DiskSessionStore(args.state_dir),
        )
    # admission control (serving/autoscale/admission.py): CLI flags
    # override the config knobs; both 0/unset = off, the byte-stable
    # status quo (no controller constructed at all)
    queue_cap = (args.queue_cap if args.queue_cap is not None
                 else cfg.admission_queue_cap)
    deadline_ms = (args.queue_deadline_ms
                   if args.queue_deadline_ms is not None
                   else cfg.admission_deadline_ms)
    admission = None
    if queue_cap or deadline_ms:
        from mamba_distributed_tpu.serving.autoscale import (
            AdmissionController,
        )

        admission = AdmissionController(queue_cap=queue_cap,
                                        default_deadline_ms=deadline_ms)
    router = RequestRouter(None, cfg, replicas=replicas, tracer=tracer,
                           retain_results=False, admission=admission,
                           session_store=session_store)
    # elastic fleet (serving/autoscale/controller.py): scale-ups spawn
    # workers exactly like the seed ones (same config/capacity/flags)
    # through a ProcessProvisioner; scale-downs drain + shut down.
    # Spawn mode only — externally-started workers are the operator's.
    autoscale = None
    if autoscale_max:
        if not args.spawn:
            ap.error("--autoscale-max needs --spawn (the provisioner "
                     "spawns new workers like the seed ones; connected "
                     "workers are externally managed)")
        import dataclasses as _dc

        from mamba_distributed_tpu.serving.autoscale import (
            AutoscaleController,
            ProcessProvisioner,
        )

        def _spawn_replica(replica_id: int, role: str):
            proc, port = spawn_worker(
                args.config, replica_id, role, capacity=args.capacity,
                tokens_per_tick=args.tokens_per_tick,
                param_seed=args.param_seed, adapters=args.adapter,
                obs_ring=(args.obs_ring if args.obs_stream else 0),
            )
            procs.append(proc)  # the rolling shutdown reaps these too
            return proc, RemoteReplica(replica_id, ("127.0.0.1", port),
                                       role=role)

        policy = _dc.replace(cfg.autoscale_policy(),
                             max_replicas=autoscale_max)
        autoscale = AutoscaleController(
            router, ProcessProvisioner(_spawn_replica), policy,
            tracer=tracer,
        )
    health = HeartbeatMonitor(router, interval_ms=args.heartbeat_ms,
                              miss_threshold=args.miss_threshold, emit=emit)
    obs_sink = None
    if args.obs_stream:
        open(args.obs_stream, "w").close()
        obs_sink = lambda rec: append_jsonl(args.obs_stream, rec)  # noqa: E731
    controller = FabricController(
        router, health=health, adapters=adapter_store, emit=emit,
        obs_pull_s=(args.obs_pull_s if args.obs_stream else 0.0),
        obs_sink=obs_sink, autoscale=autoscale,
    )
    controller.start()
    http = FabricHTTPServer(controller, args.http_host, args.http_port)
    port = http.start_background()

    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: stop.set())
    print(f"SERVE_FABRIC_READY port={port} workers={n} pid={os.getpid()}",
          flush=True)
    stop.wait()

    # rolling shutdown: drain everyone (queued work requeues while any
    # survivor accepts), wait for in-flight streams, then retire.
    # router.replicas, not the seed list: autoscaled-up workers drain
    # and retire exactly like the ones this process started with
    for rep in list(router.replicas):
        if not rep.alive:
            continue
        try:
            controller.call(
                lambda rid=rep.replica_id:
                router.drain(rid, requeue_queued=True)
            ).result(30)
        except Exception:  # noqa: BLE001 — shutdown is best-effort
            pass
    deadline = time.monotonic() + 60
    while router.pending and time.monotonic() < deadline:
        time.sleep(0.05)
    if procs:
        # spawn mode owns its workers; externally-started workers are
        # the operator's to retire (they are drained, not shut down)
        for rep in router.replicas:
            if rep.alive:
                rep.shutdown()
    for proc in procs:
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
    http.stop()
    controller.stop()
    controller.join(timeout=10)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
