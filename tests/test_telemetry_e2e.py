"""Live telemetry plane e2e over real worker subprocesses (ISSUE 17
acceptance): the fabric-wide /metrics scrape, the healthz readiness
gate, and cross-host obs shipping -> trace export from the controller's
pulled stream alone.

Reuses the test_service.py Fabric harness (worker subprocesses +
RemoteReplicas + controller + HTTP front end on loopback).  Run directly
with ``pytest -m metrics`` / ``pytest -m service``.
"""

import json
import threading
import time

import jax
import pytest

from mamba_distributed_tpu.models import init_lm_params
from tests.test_service import (
    CHUNK,
    Fabric,
    _spec,
    hybrid_cfg,
    rand_prompt,
    solo,
    tiny_cfg,
)

pytestmark = [pytest.mark.service, pytest.mark.serving, pytest.mark.obs,
              pytest.mark.metrics]


@pytest.fixture
def fabric_factory(tmp_path):
    fabrics = []

    def make(cfg, **kw):
        f = Fabric(cfg, tmp_path, **kw)
        fabrics.append(f)
        return f

    yield make
    for f in fabrics:
        f.close()


def test_fabric_metrics_scrape_e2e(fabric_factory):
    """The ISSUE 17 acceptance scrape: curl /metrics against a 2-worker
    loopback fabric returns ONE valid Prometheus exposition with
    per-replica throughput, the ITL histogram, queue depth, hybrid KV
    pages and (workers run --compile-watchdog) compile counters."""
    from mamba_distributed_tpu.obs import prom

    cfg = hybrid_cfg()
    fab = fabric_factory(cfg, worker_args=["--compile-watchdog"])
    jobs = [(rand_prompt(5 + 3 * i, seed=60 + i), 300 + i, 6)
            for i in range(4)]
    results = [None] * len(jobs)
    errors = []

    def drive(i):
        prompt, seed, max_new = jobs[i]
        try:
            results[i] = fab.stream(_spec(prompt, seed, max_new))
        except Exception as e:  # noqa: BLE001
            errors.append((i, repr(e)))

    threads = [threading.Thread(target=drive, args=(i,))
               for i in range(len(jobs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors

    status, ctype, text = fab.get_raw("/metrics")
    assert status == 200
    assert ctype == prom.CONTENT_TYPE
    parsed = prom.parse_exposition(text)  # raises on any malformed line

    # fabric-level gauges
    assert parsed["mamba_fabric_replicas"]["samples"][0][2] == 2.0
    assert parsed["mamba_fabric_ready"]["samples"][0][2] == 1.0
    assert parsed["mamba_fabric_replicas_accepting"]["samples"][0][2] == 2.0
    # the obs plane is OFF in this fabric: its counters must be absent
    assert "mamba_fabric_obs_records_pulled_total" not in parsed

    def by_replica(family):
        return {labels["replica"]: value
                for _, labels, value in parsed[family]["samples"]}

    # per-replica throughput: both workers ticked and report tok/s
    tps = by_replica("mamba_decode_tokens_per_sec")
    assert set(tps) == {"0", "1"}
    assert all(v > 0 for v in tps.values()), tps
    ticks = by_replica("mamba_ticks_total")
    assert all(v >= 1 for v in ticks.values())
    # queue depth + slot gauges come from the live worker _stats side
    assert set(by_replica("mamba_queue_depth")) == {"0", "1"}
    assert all(v == 3.0 for v in by_replica("mamba_slot_capacity").values())
    # hybrid KV page pool
    assert all(v > 0 for v in by_replica("mamba_kv_pages_capacity").values())
    # the ITL histogram crossed the wire with full sparse buckets
    itl = parsed["mamba_itl_ms"]
    assert itl["type"] == "histogram"
    counts = [v for name, labels, v in itl["samples"]
              if name == "mamba_itl_ms_count"]
    assert counts and sum(counts) >= len(jobs)  # >=1 ITL sample per job
    infs = [v for name, labels, v in itl["samples"]
            if name == "mamba_itl_ms_bucket" and labels["le"] == "+Inf"]
    assert sum(infs) == sum(counts)  # +Inf closes every series
    # compile watchdog: the jit warmup compiles were counted and shipped
    compiles = by_replica("mamba_compiles_total")
    assert set(compiles) == {"0", "1"}
    assert all(v >= 1 for v in compiles.values()), compiles
    # every sample name in the document is schema-prefixed
    assert all(name.startswith("mamba_") for name in parsed)


def test_fabric_healthz_readiness_gate(fabric_factory):
    """/healthz carries the top-level "ready" bool and flips its status
    line to 503 when zero replicas accept work — what a load balancer's
    probe reads without parsing JSON."""
    from mamba_distributed_tpu.obs import prom

    cfg = tiny_cfg()
    fab = fabric_factory(cfg, n=1)
    hz = fab.get("/healthz")
    assert hz["_status"] == 200
    assert hz["ready"] is True and hz["ok"] is True

    # drain the only replica: fabric still up, but accepting nothing
    drained = fab.post("/drain/0")
    assert drained["_status"] == 200
    hz = fab.get("/healthz")
    assert hz["_status"] == 503
    assert hz["ready"] is False
    assert hz["replicas"]["0"]["state"] == "draining"

    # /metrics stays scrapeable through the outage and says why
    status, _, text = fab.get_raw("/metrics")
    assert status == 200
    parsed = prom.parse_exposition(text)
    assert parsed["mamba_fabric_ready"]["samples"][0][2] == 0.0
    assert parsed["mamba_fabric_replicas_accepting"]["samples"][0][2] == 0.0


def test_fabric_pulled_stream_trace_export_migration(fabric_factory,
                                                     tmp_path):
    """Cross-host obs shipping end to end: ring-only workers (NO span
    files anywhere), the controller's obs_pull drain merges both rings
    into one obs_src-stamped stream, and trace_export renders the
    migrated request's cross-process flow from that single file."""
    from mamba_distributed_tpu.obs import export_chrome_trace

    cfg = hybrid_cfg(disagg_prompt_threshold=24)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    fab = fabric_factory(cfg, roles=["prefill", "decode"],
                         obs_ring=2048, obs_pull_s=0.05)
    assert fab.worker_spans == []  # ring-only: zero worker-local files

    long_prompt = rand_prompt(2 * CHUNK + 7, seed=70)
    res = fab.stream(_spec(long_prompt, 700, 6))
    assert res["tokens"] == solo(params, cfg, long_prompt, 700, 6)
    assert fab.get("/healthz")["migrations"] >= 1

    # the controller's background drain pulls both rings on its own
    # cadence — wait for records from BOTH origins to land
    deadline = time.time() + 60
    while time.time() < deadline:
        srcs = {r.get("obs_src") for r in fab.obs_records()}
        if {"replica0", "replica1"} <= srcs:
            break
        time.sleep(0.05)
    assert {"replica0", "replica1"} <= srcs, srcs

    # pulled counters surfaced on the scrape (plane is ON here)
    from mamba_distributed_tpu.obs import prom

    _, _, text = fab.get_raw("/metrics")
    parsed = prom.parse_exposition(text)
    assert parsed["mamba_fabric_obs_records_pulled_total"][
        "samples"][0][2] >= len(fab.obs_records())

    # ONE merged file -> per-origin tracks + cross-replica flow arrows
    # for the migrated request, with zero remote file access
    out = str(tmp_path / "pulled_trace.json")
    meta = export_chrome_trace([fab.obs_stream], out)
    assert meta["streams"] >= 2  # one track per obs_src origin
    assert meta["linked_requests"] >= 1  # the migrated trace id crossed
    assert meta["flow_events"] > 0
    with open(out) as f:
        assert json.load(f)["traceEvents"]
