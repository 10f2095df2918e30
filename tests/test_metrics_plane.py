"""Live telemetry plane tests: Prometheus exposition, obs-ring shipping,
compile watchdog and the tick-latency regression sentinel.

Everything here is host-only and fast except the one guarded test that
registers a real ``jax.monitoring`` listener around a real jit compile.
The e2e /metrics scrape over a live fabric lives in test_service.py
(it needs worker subprocesses); the schema drift gate is exercised from
test_cli.py.
"""

import json
import threading
import types

import pytest

from mamba_distributed_tpu.config import TelemetryConfig
from mamba_distributed_tpu.obs import (
    NULL_TRACER,
    CompileWatchdog,
    SpanTracer,
    StreamingHistogram,
    TickRegressionDetector,
    split_pulled_stream,
)
from mamba_distributed_tpu.obs import prom
from mamba_distributed_tpu.obs.export import load_jsonl
from mamba_distributed_tpu.serving.service.server import FabricController
from mamba_distributed_tpu.utils.metrics import ServingMetrics

pytestmark = [pytest.mark.obs, pytest.mark.metrics]


# ---------------------------------------------------------- exposition


@pytest.mark.fast
def test_prom_label_escaping_round_trips():
    # every character the text format escapes, in one value
    nasty = 'quo"te\\back\nnewline'
    assert prom.escape_label_value(nasty) == 'quo\\"te\\\\back\\nnewline'
    fam = prom.MetricFamily("mamba_t_total", "counter", "help text")
    fam.add(3, replica="0", role=nasty)
    parsed = prom.parse_exposition(prom.render([fam]))
    (name, labels, value), = parsed["mamba_t_total"]["samples"]
    assert name == "mamba_t_total"
    assert labels == {"replica": "0", "role": nasty}
    assert value == 3.0


@pytest.mark.fast
def test_prom_render_parse_round_trip():
    c = prom.MetricFamily("mamba_a_total", "counter", "A.")
    c.add(7, replica="0").add(9, replica="1")
    g = prom.MetricFamily("mamba_b", "gauge", "B.")
    g.add(0.5)
    parsed = prom.parse_exposition(prom.render([c, g]))
    assert parsed["mamba_a_total"]["type"] == "counter"
    assert parsed["mamba_a_total"]["help"] == "A."
    assert [v for _, _, v in parsed["mamba_a_total"]["samples"]] == [7.0, 9.0]
    assert parsed["mamba_b"]["type"] == "gauge"
    assert parsed["mamba_b"]["samples"] == [("mamba_b", {}, 0.5)]


@pytest.mark.fast
def test_prom_histogram_buckets_cumulative_inf_closed():
    h = StreamingHistogram()
    values = [0.7, 3.0, 3.5, 1e9]  # 1e9 overflows into +Inf only
    for v in values:
        h.record(v)
    fam = prom.MetricFamily("mamba_h_ms", "histogram", "H.")
    fam.add_histogram(h.to_dict(), replica="0")
    parsed = prom.parse_exposition(prom.render([fam]))["mamba_h_ms"]
    assert parsed["type"] == "histogram"
    buckets = [(labels["le"], v) for name, labels, v in parsed["samples"]
               if name.endswith("_bucket")]
    # cumulative: counts never decrease along increasing le
    counts = [v for _, v in buckets]
    assert counts == sorted(counts)
    # mandatory terminal +Inf bucket equals the total count
    assert buckets[-1][0] == "+Inf"
    assert buckets[-1][1] == len(values)
    # the overflow observation appears ONLY in +Inf (finite les < total)
    assert all(v < len(values) for _, v in buckets[:-1])
    (count,) = [v for name, _, v in parsed["samples"]
                if name.endswith("_count")]
    (total,) = [v for name, _, v in parsed["samples"]
                if name.endswith("_sum")]
    assert count == len(values)
    assert total == pytest.approx(sum(values))


@pytest.mark.fast
def test_prom_type_misuse_raises():
    with pytest.raises(ValueError):
        prom.MetricFamily("mamba_x", "timer", "bad type")
    hist = prom.MetricFamily("mamba_h", "histogram", "H.")
    with pytest.raises(ValueError):
        hist.add(1.0)
    counter = prom.MetricFamily("mamba_c_total", "counter", "C.")
    with pytest.raises(ValueError):
        counter.add_histogram({"lo": 1, "hi": 2, "growth": 2})


@pytest.mark.fast
def test_prom_gated_blocks_absent_until_present():
    """kv/goodput/compile families appear only when the summary carries
    those blocks — a watchdog-less CPU replica must not emit
    mamba_compiles_total."""
    bare = {"replica": 0, "role": "mixed",
            "summary": {"ticks": 5, "decode_tokens": 10,
                        "finished_requests": 1, "preemptions": 0},
            "histograms": {}, "stats": {}}
    parsed = prom.parse_exposition(prom.render(prom.replica_families([bare])))
    for gated in ("mamba_kv_pages_used", "mamba_serving_mfu",
                  "mamba_compiles_total", "mamba_itl_ms"):
        assert gated not in parsed
    assert parsed["mamba_ticks_total"]["samples"][0][2] == 5.0

    full = dict(bare)
    full["summary"] = dict(bare["summary"],
                           kv_pages={"used": 3, "capacity": 8,
                                     "peak_used": 5, "allocs": 9,
                                     "frees": 6},
                           compile={"compiles": 2, "compile_ms": 120.0})
    parsed = prom.parse_exposition(prom.render(prom.replica_families([full])))
    assert parsed["mamba_kv_pages_used"]["samples"][0][2] == 3.0
    assert parsed["mamba_compiles_total"]["samples"][0][2] == 2.0


@pytest.mark.fast
def test_prom_fabric_obs_counters_gated_on_plane():
    off = prom.render_fabric([], replicas=2, accepting=2, ready=True)
    assert "mamba_fabric_obs_records_pulled_total" not in off
    assert "mamba_fabric_ready 1" in off
    on = prom.render_fabric([], replicas=2, accepting=0, ready=False,
                            obs_records_pulled=10, obs_records_dropped=1)
    parsed = prom.parse_exposition(on)
    assert parsed["mamba_fabric_obs_records_pulled_total"]["samples"][0][2] \
        == 10.0
    assert parsed["mamba_fabric_ready"]["samples"][0][2] == 0.0


@pytest.mark.fast
def test_prom_content_type_pinned():
    # the scrape contract: text format 0.0.4, what Prometheus expects
    assert prom.CONTENT_TYPE == "text/plain; version=0.0.4; charset=utf-8"


# ------------------------------------------------------------ obs ring


@pytest.mark.fast
def test_ring_pull_cursor_resume():
    tr = SpanTracer(None, ring_len=64)
    tr.event("a", i=0)
    tr.event("b", i=1)
    page = tr.ring_pull(0)
    assert page["dropped"] == 0
    names = [r["name"] for r in page["records"] if r.get("kind") == "event"]
    assert names == ["a", "b"]
    cursor = page["cursor"]
    # nothing new: empty page, cursor unchanged
    again = tr.ring_pull(cursor)
    assert again["records"] == [] and again["cursor"] == cursor
    tr.event("c", i=2)
    fresh = tr.ring_pull(cursor)
    assert [r["name"] for r in fresh["records"]] == ["c"]
    assert fresh["dropped"] == 0


@pytest.mark.fast
def test_ring_pull_lapped_cursor_reports_dropped():
    tr = SpanTracer(None, ring_len=4)
    for i in range(12):
        tr.event("e", i=i)
    page = tr.ring_pull(0)
    assert len(page["records"]) == 4
    # the ring lapped the reader: the gap is explicit, never silent —
    # dropped + returned covers every record ever emitted
    assert page["dropped"] > 0
    assert page["dropped"] + len(page["records"]) == 12 + 1  # + header
    # resuming from the returned cursor is clean again
    tr.event("tail", i=99)
    nxt = tr.ring_pull(page["cursor"])
    assert [r["name"] for r in nxt["records"]] == ["tail"]
    assert nxt["dropped"] == 0


@pytest.mark.fast
def test_ring_pull_limit_pages_through():
    tr = SpanTracer(None, ring_len=64)
    for i in range(6):
        tr.event("e", i=i)
    seen, cursor = [], 0
    while True:
        page = tr.ring_pull(cursor, limit=2)
        if not page["records"]:
            break
        assert len(page["records"]) <= 2
        seen.extend(r.get("i") for r in page["records"]
                    if r.get("kind") == "event")
        cursor = page["cursor"]
    assert seen == list(range(6))


@pytest.mark.fast
def test_ring_only_tracer_touches_no_file(tmp_path):
    before = set(tmp_path.iterdir())
    tr = SpanTracer(None, ring_len=8)
    with tr.span("phase", replica=0):
        pass
    tr.event("evt")
    assert set(tmp_path.iterdir()) == before
    page = tr.ring_pull(0)
    kinds = [r["kind"] for r in page["records"]]
    # the trace_header rides the ring too — a pulled stream is mergeable
    # by obs/export.py without the worker's file
    assert "trace_header" in kinds and "span" in kinds and "event" in kinds
    # pulled records are plain jsonable dicts
    json.dumps(page["records"])


@pytest.mark.fast
def test_null_tracer_ring_pull_empty():
    page = NULL_TRACER.ring_pull(7)
    assert page == {"records": [], "cursor": 7, "dropped": 0}


# ------------------------------------------------------- jsonl rotation


@pytest.mark.fast
def test_span_rotation_rolls_once_and_load_jsonl_reads_pair(tmp_path):
    path = str(tmp_path / "spans.jsonl")
    tr = SpanTracer(path, rotate_bytes=600)
    for i in range(40):
        tr.event("e", i=i)
    rolled = tmp_path / "spans.jsonl.1"
    assert rolled.exists()
    live_recs = load_jsonl(str(rolled))
    assert live_recs, "rolled sibling must hold the older records"
    merged = load_jsonl(path)
    events = [r["i"] for r in merged if r.get("kind") == "event"]
    # oldest-first across the pair, no duplicates, and the most recent
    # events survive (rotation drops at most the .1 predecessor's
    # predecessor — here there was none)
    assert events == sorted(events)
    assert events[-1] == 39
    # the fresh live file re-stamps a header so it can stand alone
    with open(path) as f:
        first_live = json.loads(f.readline())
    assert first_live["kind"] == "trace_header"


@pytest.mark.fast
def test_span_rotation_off_never_rolls(tmp_path):
    path = str(tmp_path / "spans.jsonl")
    tr = SpanTracer(path)  # rotate_bytes=0 = never
    for i in range(200):
        tr.event("e", i=i)
    assert not (tmp_path / "spans.jsonl.1").exists()
    assert len(load_jsonl(path)) == 201  # header + events


# --------------------------------------------- controller obs shipping


class _FakeRemote:
    """RemoteReplica lookalike: ring + boot_id behind an obs_pull()."""

    def __init__(self, replica_id, boot_id="boot-a"):
        self.replica_id = replica_id
        self.alive = True
        self.boot_id = boot_id
        self.tracer = SpanTracer(None, ring_len=64)
        self.pull_cursors = []

    def obs_pull(self, cursor=0, limit=4096):
        self.pull_cursors.append(cursor)
        page = self.tracer.ring_pull(cursor, limit)
        page["boot_id"] = self.boot_id
        return page


def _controller(replicas, **kw):
    router = types.SimpleNamespace(replicas=replicas)
    ctrl = FabricController(router, **kw)
    ctrl._next_obs_pull = 0.0  # the test drives the drain directly
    return ctrl


@pytest.mark.fast
def test_controller_drain_merges_and_stamps_obs_src():
    remote = _FakeRemote(1)
    remote.tracer.event("remote_evt")
    local_tracer = SpanTracer(None, ring_len=64)
    local_tracer.event("local_evt")
    inproc = types.SimpleNamespace(
        replica_id=0, alive=True,
        engine=types.SimpleNamespace(tracer=local_tracer))
    sunk = []
    ctrl = _controller([inproc, remote], obs_pull_s=0.5,
                       obs_sink=sunk.append)
    ctrl._drain_obs()
    srcs = {r["obs_src"] for r in ctrl.obs_records}
    assert srcs == {"replica0", "replica1"}
    assert ctrl.obs_records_pulled == len(ctrl.obs_records) > 0
    assert sunk == list(ctrl.obs_records)
    # second drain: cursors resumed, nothing re-pulled
    pulled_before = ctrl.obs_records_pulled
    ctrl._next_obs_pull = 0.0
    ctrl._drain_obs()
    assert ctrl.obs_records_pulled == pulled_before


@pytest.mark.fast
def test_controller_drain_resets_cursor_on_worker_reboot():
    remote = _FakeRemote(0, boot_id="boot-a")
    remote.tracer.event("before_restart")
    ctrl = _controller([remote], obs_pull_s=0.5)
    ctrl._drain_obs()
    assert ctrl._obs_cursors[0]["boot_id"] == "boot-a"
    advanced = ctrl._obs_cursors[0]["cursor"]
    assert advanced > 0

    # the worker restarts: fresh ring, fresh boot_id, fresh seq space
    remote.boot_id = "boot-b"
    remote.tracer = SpanTracer(None, ring_len=64)
    remote.tracer.event("after_restart")
    remote.pull_cursors.clear()
    ctrl._next_obs_pull = 0.0
    ctrl._drain_obs()
    # controller noticed the boot change and re-pulled from 0, so the
    # restarted worker's early records are not skipped
    assert 0 in remote.pull_cursors
    names = [r.get("name") for r in ctrl.obs_records]
    assert "before_restart" in names and "after_restart" in names
    assert ctrl._obs_cursors[0]["boot_id"] == "boot-b"


@pytest.mark.fast
def test_controller_drain_off_is_inert():
    remote = _FakeRemote(0)
    remote.tracer.event("evt")
    ctrl = _controller([remote], obs_pull_s=0.0)
    ctrl._drain_obs()
    assert remote.pull_cursors == []
    assert len(ctrl.obs_records) == 0 and ctrl.obs_records_pulled == 0


@pytest.mark.fast
def test_controller_drain_survives_sink_and_wire_faults():
    healthy = _FakeRemote(0)
    healthy.tracer.event("evt")
    wedged = _FakeRemote(1)
    wedged.tracer.event("lost_for_now")
    wedged.obs_pull = lambda cursor=0, limit=4096: None  # wire fault

    def bad_sink(rec):
        raise OSError("disk full")

    ctrl = _controller([healthy, wedged], obs_pull_s=0.5,
                       obs_sink=bad_sink)
    ctrl._drain_obs()  # must not raise
    assert {r["obs_src"] for r in ctrl.obs_records} == {"replica0"}


@pytest.mark.fast
def test_controller_drain_counts_ring_drops():
    remote = _FakeRemote(0)
    remote.tracer = SpanTracer(None, ring_len=4)
    for i in range(12):
        remote.tracer.event("e", i=i)
    ctrl = _controller([remote], obs_pull_s=0.5)
    ctrl._drain_obs()
    assert ctrl.obs_records_dropped > 0
    assert len(ctrl.obs_records) == 4


# ------------------------------------------------------ compile watchdog


@pytest.mark.fast
def test_watchdog_thrash_fires_once_per_window_and_rearms():
    clock = [0.0]
    tracer = SpanTracer(None, ring_len=64)
    wd = CompileWatchdog(thrash_threshold=2, thrash_window_s=10.0,
                         tracer=tracer, _clock=lambda: clock[0])

    def thrash_events():
        return [r for r in tracer.ring_pull(0)["records"]
                if r.get("name") == "compile_thrash"]

    for _ in range(5):  # threshold 2 → fires at the 3rd, then stays quiet
        wd.on_compile(0.010)
    assert wd.thrash_events == 1
    assert len(thrash_events()) == 1
    assert thrash_events()[0]["threshold"] == 2

    clock[0] = 11.0  # next window: re-armed
    for _ in range(4):
        wd.on_compile(0.010)
    assert wd.thrash_events == 2
    assert len(thrash_events()) == 2


@pytest.mark.fast
def test_watchdog_drain_returns_window_deltas():
    wd = CompileWatchdog()
    wd.on_compile(0.050)
    wd.on_compile(0.030)
    n, ms = wd.drain()
    assert n == 2 and ms == pytest.approx(80.0)
    assert wd.drain() == (0, 0.0)  # zeroed after drain
    wd.on_compile(0.020)
    assert wd.drain() == (1, pytest.approx(20.0))
    # process-lifetime totals keep accumulating across drains
    assert wd.compiles == 3 and wd.compile_ms == pytest.approx(100.0)


@pytest.mark.fast
def test_watchdog_validation():
    with pytest.raises(ValueError):
        CompileWatchdog(thrash_threshold=-1)
    with pytest.raises(ValueError):
        CompileWatchdog(thrash_window_s=0.0)


def test_watchdog_counts_real_jax_compiles():
    """Integration: the jax.monitoring listener sees a real backend
    compile."""
    import jax
    import jax.numpy as jnp

    wd = CompileWatchdog()
    wd.install()
    try:
        @jax.jit
        def fresh_fn(x):  # a new callable => guaranteed cache miss
            return x * 2.0 + 1.0

        fresh_fn(jnp.ones((4,), jnp.float32)).block_until_ready()
        n, ms = wd.drain()
        assert n >= 1
        assert wd.compiles >= 1
        assert ms >= 0.0
    finally:
        wd.uninstall()


# --------------------------------------------- tick regression sentinel


@pytest.mark.fast
def test_tick_regression_breach_freezes_baseline_then_recovers():
    tracer = SpanTracer(None, ring_len=128)
    det = TickRegressionDetector(factor=2.0, alpha=0.5,
                                 baseline_alpha=0.05, warmup=2,
                                 tracer=tracer)

    def events():
        return [r["name"] for r in tracer.ring_pull(0)["records"]
                if r.get("kind") == "event"]

    det.observe_tick(10.0)
    det.observe_tick(10.0)  # warmup done: baseline == smoothed == 10
    assert det.baseline_ms == pytest.approx(10.0)
    assert not det.in_breach and events() == []

    det.observe_tick(100.0)  # smoothed 55 > 2 x ~14.5 → breach opens
    assert det.in_breach and det.breaches == 1
    assert events() == ["tick_regression"]
    frozen = det.baseline_ms
    det.observe_tick(100.0)  # still in breach: ONE event, baseline frozen
    assert events() == ["tick_regression"]
    assert det.baseline_ms == frozen  # slow must not become the new normal

    while det.in_breach:  # recovery: smoothed decays back under the bar
        det.observe_tick(10.0)
    assert events() == ["tick_regression", "tick_recovered"]
    assert det.breaches == 1
    s = det.summary()
    assert s["breaches"] == 1 and s["in_breach"] is False


@pytest.mark.fast
def test_tick_regression_ignores_garbage_and_validates():
    det = TickRegressionDetector(factor=2.0, warmup=1)
    det.observe_tick(float("nan"))
    det.observe_tick(-5.0)
    assert det.ticks == 0
    with pytest.raises(ValueError):
        TickRegressionDetector(factor=1.0)
    with pytest.raises(ValueError):
        TickRegressionDetector(alpha=0.1, baseline_alpha=0.1)  # must lag
    with pytest.raises(ValueError):
        TickRegressionDetector(warmup=0)


@pytest.mark.fast
def test_tick_regression_from_config():
    assert TickRegressionDetector.from_config(TelemetryConfig()) is None
    det = TickRegressionDetector.from_config(
        TelemetryConfig(tick_regression_factor=3.0,
                        tick_regression_warmup=4))
    assert det is not None and det.factor == 3.0 and det.warmup == 4


# ------------------------------------------- byte-stability when off


@pytest.mark.fast
def test_tick_records_byte_stable_without_compile_plane(tmp_path):
    off = ServingMetrics(capacity=2,
                         jsonl_path=str(tmp_path / "off.jsonl"))
    off.record_tick(occupied=1, queue_depth=0, tokens_emitted=2,
                    dt_s=0.01)
    with open(tmp_path / "off.jsonl") as f:
        rec = json.loads(f.readlines()[-1])
    assert "compiles" not in rec and "compile_ms" not in rec
    assert off.summary()["compile"] is None

    on = ServingMetrics(capacity=2, jsonl_path=str(tmp_path / "on.jsonl"))
    on.configure_compile()
    on.record_tick(occupied=1, queue_depth=0, tokens_emitted=2,
                   dt_s=0.01, compiles=2, compile_ms=50.0)
    with open(tmp_path / "on.jsonl") as f:
        rec = json.loads(f.readlines()[-1])
    assert rec["compiles"] == 2 and rec["compile_ms"] == 50.0
    assert on.summary()["compile"] == {"compiles": 2, "compile_ms": 50.0}


@pytest.mark.fast
def test_telemetry_config_plane_knobs_validate():
    TelemetryConfig(span_rotate_bytes=1 << 20,
                    compile_watchdog=True,
                    compile_thrash_threshold=8,
                    compile_thrash_window_s=30.0,
                    tick_regression_factor=2.0,
                    tick_ewma_alpha=0.2,
                    tick_regression_warmup=16)
    with pytest.raises(ValueError):
        TelemetryConfig(span_rotate_bytes=-1)
    with pytest.raises(ValueError):
        TelemetryConfig(compile_thrash_threshold=-1)
    with pytest.raises(ValueError):
        TelemetryConfig(compile_thrash_window_s=0.0)
    with pytest.raises(ValueError):
        TelemetryConfig(tick_regression_factor=1.0)
    with pytest.raises(ValueError):
        TelemetryConfig(tick_ewma_alpha=0.0)
    with pytest.raises(ValueError):
        TelemetryConfig(tick_regression_warmup=0)


# -------------------------------------------------- pulled-stream export


@pytest.mark.fast
def test_split_pulled_stream_groups_by_src():
    records = [
        {"kind": "trace_header", "obs_src": "replica0", "pid": 1},
        {"kind": "span", "name": "a", "obs_src": "replica0"},
        {"kind": "trace_header", "obs_src": "replica1", "pid": 2},
        {"kind": "span", "name": "b", "obs_src": "replica1"},
        {"kind": "event", "name": "untagged"},
    ]
    streams, labels = split_pulled_stream(records)
    assert len(streams) == len(labels) == 3
    by_label = dict(zip(labels, streams))
    assert {r["name"] for r in by_label["replica0"]
            if r["kind"] == "span"} == {"a"}
    assert {r["name"] for r in by_label["replica1"]
            if r["kind"] == "span"} == {"b"}
    assert by_label["local"][0]["name"] == "untagged"


@pytest.mark.fast
def test_ring_pull_concurrent_writer_safe():
    """A writer hammering the ring while a reader pages through it must
    never corrupt a page (the controller drains on its own thread)."""
    tr = SpanTracer(None, ring_len=256)
    stop = threading.Event()

    def writer():
        i = 0
        while not stop.is_set():
            tr.event("e", i=i)
            i += 1

    t = threading.Thread(target=writer, daemon=True)
    t.start()
    try:
        cursor, pulled = 0, 0
        for _ in range(200):
            page = tr.ring_pull(cursor, limit=64)
            assert len(page["records"]) <= 64
            assert page["cursor"] >= cursor
            cursor = page["cursor"]
            pulled += len(page["records"])
        assert pulled > 0
    finally:
        stop.set()
        t.join(timeout=5)
