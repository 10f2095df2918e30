"""Replica worker entrypoint: one serving engine behind a TCP port.

One process per replica of the cross-host fabric (docs/SERVING.md
"Deploying as a service").  The worker builds its engine from a config
JSON (``serving.service.worker.config_to_json`` — identical config in
every process) and a shared ``--param-seed`` (identical weights), binds
a loopback/TCP listener, prints one READY line:

  SERVE_WORKER_READY replica=0 role=mixed port=41733 pid=12345

and then serves RPC frames from the fabric front end
(scripts/serve_fabric.py) until shutdown.  SIGTERM drains: no new
placements, resident work finishes, then the process exits — the
rolling-restart contract.

  # a 2-worker loopback fabric by hand:
  python scripts/serve_worker.py --config cfg.json --replica-id 0 &
  python scripts/serve_worker.py --config cfg.json --replica-id 1 &
  python scripts/serve_fabric.py --config cfg.json \
      --workers 127.0.0.1:PORT0,127.0.0.1:PORT1

Real checkpoints: pass ``--checkpoint DIR`` to serve trained params
instead of the seed-initialized ones (the seed path is the parity/CI
harness — every process derives bit-identical weights with zero
checkpoint I/O).
"""

from __future__ import annotations

import argparse
import os
import signal
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _start_metrics_server(host: str, port: int, metrics, worker, *,
                          replica_id: int, role: str) -> int:
    """Per-worker Prometheus exposition on its own daemon thread
    (stdlib http.server): the same replica families the front end's
    fabric-wide /metrics renders, scoped to this one engine — a
    per-host scrape target that survives a front-end outage.  Returns
    the bound port (``port=0`` picks an ephemeral one)."""
    import http.server
    import threading

    from mamba_distributed_tpu.obs import prom

    class _Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):  # noqa: N802 — stdlib handler name
            if self.path.rstrip("/") not in ("", "/metrics"):
                self.send_error(404)
                return
            snap = {
                "replica": replica_id, "role": role,
                "summary": metrics.summary(),
                "histograms": metrics.histogram_dicts(),
                "stats": worker._stats(),
            }
            body = prom.render(prom.replica_families([snap])).encode("utf-8")
            self.send_response(200)
            self.send_header("Content-Type", prom.CONTENT_TYPE)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *_args):  # silence per-scrape stderr spam
            pass

    srv = http.server.ThreadingHTTPServer((host, port), _Handler)
    threading.Thread(target=srv.serve_forever, daemon=True,
                     name="worker-metrics").start()
    return srv.server_address[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument("--config", metavar="PATH",
                     help="ModelConfig JSON (worker.config_to_json)")
    src.add_argument("--preset", metavar="NAME",
                     help="named preset instead of a config JSON")
    ap.add_argument("--replica-id", type=int, default=0)
    ap.add_argument("--role", default="mixed",
                    choices=["mixed", "prefill", "decode"],
                    help="disaggregated-tier role (docs/SERVING.md)")
    ap.add_argument("--capacity", type=int, default=4,
                    help="slot-pool capacity of this replica")
    ap.add_argument("--tokens-per-tick", type=int, default=8)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="listen port (0 = ephemeral; see READY line)")
    ap.add_argument("--param-seed", type=int, default=0,
                    help="PRNG seed for the (shared) param init — every "
                         "worker and the parity harness must agree")
    ap.add_argument("--checkpoint", default=None, metavar="DIR",
                    help="serve trained params from this checkpoint "
                         "(Orbax dir or reference .pt) instead of "
                         "seed-initialized ones — requires --preset")
    ap.add_argument("--adapter", action="append", default=[],
                    metavar="NAME=PATH",
                    help="preload a LoRA adapter: NAME=path-to-npz "
                         "(serving.adapters.save_adapter_file format); "
                         "repeatable.  Needs cfg.lora_max_adapters > 0 "
                         "(docs/SERVING.md 'Multi-tenant LoRA')")
    ap.add_argument("--jsonl", default=None, metavar="PATH",
                    help="this replica's serving_tick/request stream "
                         "(obs_report.py input)")
    ap.add_argument("--spans", default=None, metavar="PATH",
                    help="this replica's span stream (trace_export.py "
                         "merges it with the server's)")
    ap.add_argument("--span-rotate-bytes", type=int, default=0,
                    metavar="N",
                    help="roll the --spans jsonl to <path>.1 when it "
                         "would exceed N bytes (0 = never; one rolled "
                         "generation is kept and obs/export.load_jsonl "
                         "reads the pair in order)")
    ap.add_argument("--obs-ring", type=int, default=0, metavar="N",
                    help="keep the last N span/event records in memory "
                         "for the fabric's obs_pull RPC (wire v5) — the "
                         "controller drains them into one merged stream, "
                         "so a ring-only worker (--obs-ring without "
                         "--spans) ships live telemetry with ZERO local "
                         "files")
    ap.add_argument("--metrics-port", type=int, default=None,
                    metavar="PORT",
                    help="additionally expose THIS worker's Prometheus "
                         "/metrics on PORT (0 = ephemeral; see the READY "
                         "line) — per-host scrapers keep working when "
                         "the front end is down")
    ap.add_argument("--compile-watchdog", action="store_true",
                    help="count/time every XLA backend compile "
                         "(jax.monitoring), stamping compiles/"
                         "compile_ms on tick records and /metrics")
    ap.add_argument("--compile-thrash-threshold", type=int, default=0,
                    metavar="N",
                    help="raise one compile_thrash obs event per window "
                         "when more than N compiles land in it (0 = "
                         "never; needs --compile-watchdog)")
    ap.add_argument("--compile-thrash-window-s", type=float, default=60.0,
                    metavar="S", help="compile-thrash window length")
    ap.add_argument("--tick-regression-factor", type=float, default=0.0,
                    metavar="F",
                    help="emit tick_regression/tick_recovered obs events "
                         "when the EWMA tick latency exceeds F x its "
                         "rolling baseline (0 = off; obs/slo.py)")
    ap.add_argument("--state-dir", default=None, metavar="DIR",
                    help="durable session store for this engine "
                         "(docs/SERVING.md 'Durable sessions'): the "
                         "admission valve PARKS displaced streams here "
                         "instead of holding them in host RAM, and "
                         "park/resume_parked RPCs round-trip through "
                         "it.  TTL/budget come from cfg.session_ttl_s "
                         "and cfg.session_host_bytes")
    args = ap.parse_args()

    import jax

    from mamba_distributed_tpu.utils.platform import configure_compile_cache

    configure_compile_cache()

    from mamba_distributed_tpu.config import get_preset
    from mamba_distributed_tpu.models import init_lm_params
    from mamba_distributed_tpu.obs import NULL_TRACER, SpanTracer
    from mamba_distributed_tpu.serving import EngineReplica
    from mamba_distributed_tpu.serving.service.worker import (
        WorkerServer,
        config_from_json,
    )
    from mamba_distributed_tpu.utils.metrics import ServingMetrics

    if args.checkpoint:
        if not args.preset:
            ap.error("--checkpoint needs --preset (the preset the "
                     "checkpoint was trained with)")
        from eval import load_custom

        params, cfg = load_custom(args.checkpoint, args.preset)
    else:
        cfg = (config_from_json(args.config) if args.config
               else get_preset(args.preset).model)
        params = init_lm_params(jax.random.PRNGKey(args.param_seed), cfg)
    metrics = ServingMetrics(args.capacity, jsonl_path=args.jsonl,
                             replica=args.replica_id)
    # a ring-only tracer (--obs-ring, no --spans) touches no files at
    # all: the controller's obs_pull drain is its only consumer
    if args.spans or args.obs_ring:
        tracer = SpanTracer(args.spans, ring_len=args.obs_ring,
                            rotate_bytes=args.span_rotate_bytes)
    else:
        tracer = NULL_TRACER
    engine_kw = {}
    if args.compile_watchdog:
        from mamba_distributed_tpu.obs import CompileWatchdog

        watchdog = CompileWatchdog(
            thrash_threshold=args.compile_thrash_threshold,
            thrash_window_s=args.compile_thrash_window_s,
            tracer=tracer,
        )
        watchdog.install()
        engine_kw["compile_watchdog"] = watchdog
    if args.tick_regression_factor:
        from mamba_distributed_tpu.obs import TickRegressionDetector

        engine_kw["tick_regression"] = TickRegressionDetector(
            factor=args.tick_regression_factor, tracer=tracer)
    if args.adapter:
        from mamba_distributed_tpu.serving.adapters import (
            AdapterRegistry,
            load_adapter_file,
        )

        if cfg.lora_max_adapters <= 0:
            ap.error("--adapter needs a config with lora_max_adapters "
                     "> 0 (multi-tenant LoRA serving, docs/SERVING.md)")
        registry = AdapterRegistry(cfg, params)
        for spec in args.adapter:
            name, _, path = spec.partition("=")
            if not name or not path:
                ap.error(f"--adapter expects NAME=PATH, got {spec!r}")
            registry.register(name, load_adapter_file(path))
        engine_kw["adapters"] = registry
    if args.state_dir:
        from mamba_distributed_tpu.serving.sessions import (
            DiskSessionStore,
            SessionStore,
        )

        engine_kw["session_store"] = SessionStore(
            ttl_s=float(cfg.session_ttl_s),
            host_bytes=int(cfg.session_host_bytes),
            disk=DiskSessionStore(args.state_dir),
        )
    replica = EngineReplica(
        args.replica_id, params, cfg, metrics=metrics, tracer=tracer,
        role=args.role, capacity=args.capacity, retain_results=False,
        tokens_per_tick=args.tokens_per_tick, **engine_kw,
    )
    worker = WorkerServer(replica, args.host, args.port)
    metrics_port = ""
    if args.metrics_port is not None:
        port = _start_metrics_server(
            args.host, args.metrics_port, metrics, worker,
            replica_id=args.replica_id, role=args.role)
        metrics_port = f" metrics_port={port}"
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: worker.request_term())
    print(
        f"SERVE_WORKER_READY replica={args.replica_id} role={args.role} "
        f"port={worker.port} pid={os.getpid()}{metrics_port}",
        flush=True,
    )
    worker.serve_forever()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
