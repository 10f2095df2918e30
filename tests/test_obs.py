"""Observability tests: histogram math, span tracer, sentinels, and the
no-new-traces contract.

The load-bearing assertions are the trace-count pins: enabling spans +
sentinels must add ZERO jit compilations to the train step and the
serving decode tick — the whole obs/ layer is host-side by construction,
and these tests keep it that way.
"""

import dataclasses
import json
import os
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mamba_distributed_tpu.config import ModelConfig, TelemetryConfig
from mamba_distributed_tpu.models import init_lm_params
from mamba_distributed_tpu.obs import (
    NULL_TRACER,
    DivergenceError,
    DivergenceSentinel,
    FlightRecorder,
    SpanTracer,
    StreamingHistogram,
)
from mamba_distributed_tpu.serving import GenerationRequest, ServingEngine
from mamba_distributed_tpu.utils.metrics import ServingMetrics

# the obs marker covers the whole file; fast (the sub-2-minute inner-loop
# tier) goes per-test on the host-only unit tests — the Trainer/engine
# integration tests below each compile real jit steps and belong to the
# unmarked middle tier
pytestmark = [pytest.mark.obs]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))

from obs_report import build_report, format_report, load_events  # noqa: E402
from obs_report import main as obs_report_main  # noqa: E402


# -------------------------------------------------------------- histogram


@pytest.mark.fast
def test_histogram_single_sample_is_exact():
    h = StreamingHistogram()
    h.record(5.0)
    for q in (0, 50, 95, 99, 100):
        assert h.percentile(q) == 5.0  # clamped to [min, max]
    assert h.mean == 5.0 and h.count == 1


@pytest.mark.fast
def test_histogram_empty():
    h = StreamingHistogram()
    assert h.percentile(50) is None and h.mean is None
    assert h.summary()["count"] == 0 and h.summary()["p99"] is None


@pytest.mark.fast
def test_histogram_percentiles_within_relative_error():
    h = StreamingHistogram()
    values = [float(v) for v in range(1, 101)]  # 1..100
    for v in values:
        h.record(v)
    g = h.growth
    for q, true in [(50, 50.0), (95, 95.0), (99, 99.0)]:
        got = h.percentile(q)
        assert true / g <= got <= true * g, (q, got)
    # extremes are exact (min/max clamp)
    assert h.percentile(0) >= 1.0 and h.percentile(100) == 100.0


@pytest.mark.fast
def test_histogram_percentiles_monotonic_in_q():
    h = StreamingHistogram()
    rng = np.random.default_rng(0)
    for v in rng.lognormal(mean=2.0, sigma=1.5, size=500):
        h.record(float(v))
    qs = [0, 10, 25, 50, 75, 90, 95, 99, 100]
    ps = [h.percentile(q) for q in qs]
    assert ps == sorted(ps)


@pytest.mark.fast
def test_histogram_merge_counts_and_monotonicity():
    """Merging equals recording the combined stream: counts/totals add,
    and every percentile of the merged histogram matches a histogram fed
    both streams directly (satellite: monotonicity under merges)."""
    a, b, both = (StreamingHistogram() for _ in range(3))
    rng = np.random.default_rng(1)
    xs = [float(v) for v in rng.lognormal(1.0, 1.0, size=200)]
    ys = [float(v) for v in rng.lognormal(3.0, 0.5, size=300)]
    for v in xs:
        a.record(v)
        both.record(v)
    for v in ys:
        b.record(v)
        both.record(v)
    a.merge(b)
    assert a.count == both.count == 500
    assert a.total == pytest.approx(both.total)
    assert a.vmin == both.vmin and a.vmax == both.vmax
    for q in (5, 50, 95, 99):
        assert a.percentile(q) == pytest.approx(both.percentile(q))
    ps = [a.percentile(q) for q in (50, 95, 99)]
    assert ps == sorted(ps)


@pytest.mark.fast
def test_histogram_merge_rejects_mismatched_geometry():
    with pytest.raises(ValueError, match="geometry"):
        StreamingHistogram().merge(StreamingHistogram(lo=1.0))


@pytest.mark.fast
def test_histogram_json_round_trip():
    h = StreamingHistogram()
    for v in (0.5, 2.0, 2.0, 70.0, 1e9):  # incl. an overflow-bucket value
        h.record(v)
    h2 = StreamingHistogram.from_dict(json.loads(json.dumps(h.to_dict())))
    assert h2.count == h.count and h2.total == pytest.approx(h.total)
    for q in (0, 50, 99, 100):
        assert h2.percentile(q) == h.percentile(q)


@pytest.mark.fast
def test_histogram_weighted_and_nonfinite():
    h = StreamingHistogram()
    h.record(10.0, n=7)
    h.record(float("nan"))
    h.record(float("inf"))
    h.record(3.0, n=0)
    assert h.count == 7 and h.percentile(99) == 10.0


@pytest.mark.fast
def test_histogram_out_of_range_clamps_to_observed():
    h = StreamingHistogram(lo=1.0, hi=100.0)
    h.record(0.25)  # underflow bucket
    h.record(4000.0)  # overflow bucket
    assert h.percentile(0) == 0.25
    assert h.percentile(100) == 4000.0


# ----------------------------------------------------------------- tracer


@pytest.mark.fast
def test_span_tracer_nesting_and_attrs(tmp_path):
    path = str(tmp_path / "events.jsonl")
    t = SpanTracer(path)
    with t.span("outer", step=3):
        with t.span("inner"):
            pass
    t.event("mark", loss=float("nan"))
    ev = load_events([path])
    header = ev.pop(0)  # first write stamps the wall-clock epoch
    assert header["kind"] == "trace_header" and header["wall_t0_s"] > 0
    inner, outer, mark = ev
    assert inner["name"] == "inner" and inner["depth"] == 1
    assert inner["parent"] == "outer"
    assert outer["name"] == "outer" and outer["depth"] == 0
    assert outer["step"] == 3
    assert outer["dur_ms"] >= inner["dur_ms"] >= 0
    assert mark["kind"] == "event" and mark["loss"] is None  # NaN -> null


@pytest.mark.fast
def test_span_tracer_records_on_exception(tmp_path):
    t = SpanTracer(str(tmp_path / "e.jsonl"))
    with pytest.raises(RuntimeError):
        with t.span("dies"):
            raise RuntimeError("boom")
    (rec,) = [e for e in load_events([str(tmp_path / "e.jsonl")])
              if e["kind"] == "span"]
    assert rec["name"] == "dies"


@pytest.mark.fast
def test_span_tracer_resume_preserves_history(tmp_path):
    """A rebuilt tracer truncates on first write UNLESS preserve_history()
    ran (the checkpoint-resume / --auto-restart path, same contract as
    MetricsLogger) — the pre-crash spans are the post-mortem artifact."""
    path = str(tmp_path / "events.jsonl")

    def span_names():
        return [e["name"] for e in load_events([path])
                if e["kind"] == "span"]

    t = SpanTracer(path)
    with t.span("before_crash"):
        pass
    t2 = SpanTracer(path)  # fresh run: truncates on first write
    with t2.span("fresh"):
        pass
    assert span_names() == ["fresh"]
    t3 = SpanTracer(path)  # resumed run: appends
    t3.preserve_history()
    with t3.span("after_resume"):
        pass
    assert span_names() == ["fresh", "after_resume"]
    # each tracer stamped its own wall-clock epoch header, so the
    # resumed tracer's restarted t_ms offsets stay alignable
    headers = [e for e in load_events([path])
               if e["kind"] == "trace_header"]
    assert len(headers) == 2
    NULL_TRACER.preserve_history()  # must exist on the disabled tracer too


@pytest.mark.fast
def test_telemetry_config_rejects_overflow_without_sentinel():
    with pytest.raises(ValueError, match="sentinel"):
        TelemetryConfig(sentinel=False, overflow_threshold=1.0)
    with pytest.raises(ValueError, match=">= 0"):
        TelemetryConfig(overflow_threshold=-1.0)
    with pytest.raises(ValueError, match="flight_recorder_len"):
        TelemetryConfig(flight_recorder_len=0)


@pytest.mark.fast
def test_null_tracer_is_noop(tmp_path):
    with NULL_TRACER.span("anything", x=1):
        pass
    NULL_TRACER.event("mark")
    assert not NULL_TRACER.enabled
    assert list(tmp_path.iterdir()) == []


# ------------------------------------------- flight recorder + sentinel


@pytest.mark.fast
def test_flight_recorder_ring_and_dump(tmp_path):
    fr = FlightRecorder(capacity=3)
    for i in range(5):
        fr.record("train_step", step=i, loss=float(i))
    assert len(fr) == 3
    assert [e["step"] for e in fr.events()] == [2, 3, 4]
    path = fr.dump(str(tmp_path / "fr.json"), reason="test")
    doc = json.load(open(path))
    assert doc["reason"] == "test" and doc["capacity"] == 3
    assert [e["step"] for e in doc["events"]] == [2, 3, 4]


@pytest.mark.fast
def test_sentinel_divergence_dumps_once(tmp_path):
    path = str(tmp_path / "flight_record.json")
    s = DivergenceSentinel(path, capacity=4)
    for i in range(6):
        assert not s.observe_step(i, loss=4.0 - 0.1 * i, grad_norm=1.0)
    assert s.observe_step(6, loss=float("nan"), grad_norm=1.0)
    doc = json.load(open(path))
    assert "non-finite" in doc["reason"] and "step 6" in doc["reason"]
    assert len(doc["events"]) == 4  # bounded ring, not the whole run
    assert doc["events"][-1]["loss"] is None  # NaN serialized as null
    # a later crash must not overwrite the divergence dump
    s.on_crash(RuntimeError("later"))
    assert "non-finite" in json.load(open(path))["reason"]


@pytest.mark.fast
def test_sentinel_without_dump_path_still_detects():
    s = DivergenceSentinel(None)
    assert s.observe_step(0, loss=float("inf"), grad_norm=1.0)
    assert s.dumped_to is None


@pytest.mark.fast
def test_sentinel_overflow_accumulates():
    s = DivergenceSentinel(None)
    s.observe_step(0, 1.0, 0.5, overflow=0)
    s.observe_step(1, 1.0, 9.0, overflow=1)
    s.observe_step(2, 1.0, 9.5, overflow=1)
    assert s.overflow_count == 2
    assert s.flight.events()[-1]["overflow_total"] == 2


# -------------------------------------------------- trainer integration


def _trainer_cfg(tmp, **telemetry):
    from tests.test_parallel import make_cfg

    cfg = make_cfg(tmp, micro=4, accum=1, T=32)
    return dataclasses.replace(cfg, telemetry=TelemetryConfig(**telemetry))


def test_trainer_telemetry_zero_extra_traces(tmp_path):
    """Acceptance pin (train half): spans + sentinels add zero jit
    compilations to the train step (and eval step)."""
    from mamba_distributed_tpu.training import Trainer
    from mamba_distributed_tpu.training.train_step import TRACE_COUNTS

    t = Trainer(_trainer_cfg(tmp_path / "base", sentinel=False), verbose=False)
    t.run(max_steps=2)
    base = dict(TRACE_COUNTS)

    t = Trainer(_trainer_cfg(tmp_path / "tele", spans=True, sentinel=True),
                verbose=False)
    t.run(max_steps=2)
    delta = {k: TRACE_COUNTS[k] - base[k] for k in base}
    # each Trainer builds (and traces) its own step exactly once; the
    # telemetry-enabled trainer must not trace any more than the baseline
    assert delta == {"train_step": 1, "eval_step": 1}, delta

    ev = load_events([os.path.join(t.cfg.log_dir, "events.jsonl")])
    names = {e["name"] for e in ev if e["kind"] == "span"}
    assert {"data_load", "train_step", "eval"} <= names
    # sentinel saw every step, nothing diverged, no dump
    assert len(t.sentinel.flight) >= 2
    assert t.sentinel.dumped_to is None
    assert not os.path.exists(
        os.path.join(t.cfg.log_dir, "flight_record.json")
    )


def test_trainer_divergence_halts_and_dumps(tmp_path):
    from mamba_distributed_tpu.training import Trainer

    t = Trainer(_trainer_cfg(tmp_path, sentinel=True), verbose=False)
    real_step = t.train_step
    def nan_step(params, opt_state, x, y):
        params, opt_state, _, grad_norm = real_step(params, opt_state, x, y)
        return params, opt_state, jnp.float32(float("nan")), grad_norm
    t.train_step = nan_step
    with pytest.raises(DivergenceError, match="step 0"):
        t.run(max_steps=2)
    doc = json.load(open(os.path.join(t.cfg.log_dir, "flight_record.json")))
    assert "non-finite" in doc["reason"]
    kinds = {e["kind"] for e in doc["events"]}
    assert "train_step" in kinds and "val" in kinds


def test_trainer_overflow_counter(tmp_path):
    """Opt-in on-device overflow flag: a microscopic threshold trips on
    every step and the host counter accumulates (and the loop still
    runs — overflow is a signal, not a failure)."""
    from mamba_distributed_tpu.training import Trainer

    t = Trainer(_trainer_cfg(tmp_path, overflow_threshold=1e-9),
                verbose=False)
    t.run(max_steps=2)
    assert t.sentinel.overflow_count == 2
    assert t.sentinel.flight.events()[-1]["overflow"] == 1


def test_trainer_crash_dumps_flight_record(tmp_path):
    from mamba_distributed_tpu.training import Trainer

    t = Trainer(_trainer_cfg(tmp_path, sentinel=True), verbose=False)

    def boom(*a, **k):
        raise RuntimeError("loader died")

    t.run(max_steps=1)  # one clean step feeds the ring
    t._global_batch = boom
    with pytest.raises(RuntimeError, match="loader died"):
        t.run(max_steps=2)
    doc = json.load(open(os.path.join(t.cfg.log_dir, "flight_record.json")))
    assert doc["reason"].startswith("crash: RuntimeError")
    assert any(e["kind"] == "train_step" for e in doc["events"])


# -------------------------------------------------- serving integration


def _tiny_serving(layer_count=2):
    cfg = ModelConfig(d_model=32, n_layer=layer_count, vocab_size=64,
                      ssm_layer="mamba2", headdim=8, chunk_size=16,
                      d_state=16, compute_dtype="float32")
    return cfg, init_lm_params(jax.random.PRNGKey(0), cfg)


def test_engine_request_telemetry_and_stream(tmp_path):
    cfg, params = _tiny_serving()
    jsonl = str(tmp_path / "serving.jsonl")
    tracer = SpanTracer(str(tmp_path / "events.jsonl"))
    metrics = ServingMetrics(capacity=2, jsonl_path=jsonl)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2,
                        metrics=metrics, tracer=tracer)
    budgets = [5, 3, 4, 6]
    eng.run([GenerationRequest(prompt_ids=np.ones(4 + i, np.int32),
                               max_new_tokens=budgets[i],
                               key=jax.random.PRNGKey(i))
             for i in range(4)])
    s = metrics.summary()
    lat = s["latency"]
    assert s["finished_requests"] == 4
    assert lat["queue_wait_ms"]["count"] == 4
    assert lat["ttft_ms"]["count"] == 4
    # one ITL observation per generated token after each request's first
    assert lat["itl_ms"]["count"] == sum(b - 1 for b in budgets)
    for m in lat.values():
        assert m["p50"] is not None and m["p50"] <= m["p95"] <= m["p99"]
    # TTFT includes queue wait by definition (stamps share t_submit)
    assert lat["ttft_ms"]["p50"] >= lat["queue_wait_ms"]["p50"]
    # satellite: throughput fields present in summary()
    assert s["prefill_tokens_per_sec"] > 0 and s["mean_tick_ms"] > 0

    recs = load_events([jsonl])
    reqs = [r for r in recs if r["kind"] == "request"]
    assert len(reqs) == 4 and len(
        [r for r in recs if r["kind"] == "serving_tick"]) == s["ticks"]
    for r in reqs:
        assert r["queue_wait_ms"] <= r["ttft_ms"] <= r["e2e_ms"]
        assert r["itl_hist"]["count"] == r["new_tokens"] - 1
    spans = {e["name"] for e in load_events([str(tmp_path / "events.jsonl")])
             if e["kind"] == "span"}
    assert {"serving_admit", "serving_tick"} <= spans


def test_engine_telemetry_zero_extra_traces(tmp_path):
    """Acceptance pin (serving half): telemetry (tracer + jsonl metrics +
    request stamps) adds zero jit compilations to prefill and the decode
    tick.  Own model shape so the jit cache can't already hold it."""
    from mamba_distributed_tpu.serving.engine import TRACE_COUNTS

    cfg = ModelConfig(d_model=16, n_layer=2, vocab_size=32, ssm_layer="mamba2",
                      headdim=4, chunk_size=8, d_state=8,
                      compute_dtype="float32")
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    reqs = lambda: [GenerationRequest(prompt_ids=np.ones(4, np.int32),
                                      max_new_tokens=3, top_k=16,
                                      key=jax.random.PRNGKey(i))
                    for i in range(3)]
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2,
                        max_top_k=16)
    eng.run(reqs())
    base = dict(TRACE_COUNTS)
    metrics = ServingMetrics(capacity=2, jsonl_path=str(tmp_path / "s.jsonl"))
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2,
                        max_top_k=16, metrics=metrics,
                        tracer=SpanTracer(str(tmp_path / "e.jsonl")))
    eng.run(reqs())
    assert TRACE_COUNTS == base  # zero additional compilations
    assert metrics.summary()["latency"]["ttft_ms"]["count"] == 3


# ------------------------------------------------------------ obs_report


@pytest.mark.fast
def test_obs_report_exact_request_percentiles():
    """queue-wait/TTFT percentiles are exact (scalars in the records)."""
    events = [
        {"kind": "request", "request_id": i, "prompt_tokens": 4,
         "new_tokens": 8, "finish_reason": "length",
         "queue_wait_ms": float(i + 1), "ttft_ms": float(10 * (i + 1)),
         "e2e_ms": float(100 * (i + 1))}
        for i in range(100)  # queue waits 1..100
    ]
    r = build_report(events)["requests"]
    assert r["count"] == 100 and r["finish_reasons"] == {"length": 100}
    assert r["queue_wait_ms"]["p50"] == 50.0
    assert r["queue_wait_ms"]["p95"] == 95.0
    assert r["queue_wait_ms"]["p99"] == 99.0
    assert r["ttft_ms"]["p99"] == 990.0
    assert r["itl_ms"] is None  # no histograms in these records


@pytest.mark.fast
def test_obs_report_merges_itl_histograms():
    def req(rid, itl_values):
        h = StreamingHistogram()
        for v in itl_values:
            h.record(v)
        return {"kind": "request", "request_id": rid, "new_tokens": 9,
                "finish_reason": "length", "queue_wait_ms": 1.0,
                "ttft_ms": 2.0, "e2e_ms": 3.0, "itl_hist": h.to_dict()}

    events = [req(0, [10.0] * 8), req(1, [20.0] * 8)]
    itl = build_report(events)["requests"]["itl_ms"]
    assert itl["count"] == 16
    g = StreamingHistogram().growth
    assert 10.0 / g <= itl["p50"] <= 10.0 * g
    assert 20.0 / g <= itl["p99"] <= 20.0 * g


def test_obs_report_round_trip_through_files(tmp_path):
    """jsonl round-trip (satellite): a real serve() stream + a span
    stream land in files, obs_report ingests them and prints the
    latency-percentile and phase tables (acceptance criterion)."""
    cfg, params = _tiny_serving()
    jsonl = str(tmp_path / "serving.jsonl")
    events = str(tmp_path / "events.jsonl")
    metrics = ServingMetrics(capacity=2, jsonl_path=jsonl)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2,
                        metrics=metrics, tracer=SpanTracer(events))
    consumed = sum(1 for _ in eng.serve(
        [GenerationRequest(prompt_ids=np.ones(3 + i, np.int32),
                           max_new_tokens=4, key=jax.random.PRNGKey(i))
         for i in range(3)]
    ))
    assert consumed == 12  # serve() streamed every token
    report = build_report(load_events([jsonl, events]))
    assert report["requests"]["count"] == 3
    for metric in ("queue_wait_ms", "ttft_ms"):
        for q in ("p50", "p95", "p99"):
            assert report["requests"][metric][q] is not None
    assert report["requests"]["itl_ms"]["count"] == 9
    assert report["serving"]["decode_tokens"] == 12
    assert "serving_tick" in report["spans"]
    text = format_report(report)
    assert "queue_wait_ms" in text and "p99" in text and "phase" in text
    # in-process report == CLI report (the script is the product surface)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "obs_report.py"),
         jsonl, events, "--json"],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout)["requests"] == json.loads(
        json.dumps(report["requests"])
    )


# One driver per feature: run the engine (or the router) in process on
# the tiny model with the operator's stream on, and return the stream
# files plus the lines the report must show — each number in them is the
# engine's own counter, so the block is checked against its source.


def _request(prompt_len, max_new, seed=0, **kw):
    return GenerationRequest(
        prompt_ids=np.arange(prompt_len, dtype=np.int32) % 7,
        max_new_tokens=max_new, key=jax.random.PRNGKey(seed), **kw)


def _drive_prefill(tmp_path):
    """Chunked prefill of one long prompt beside a short one."""
    cfg, params = _tiny_chunked_serving()
    jsonl = str(tmp_path / "s.jsonl")
    metrics = ServingMetrics(capacity=2, jsonl_path=jsonl)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2,
                        metrics=metrics)
    eng.run([_request(40, 3), _request(5, 3, seed=1)])
    s = metrics.summary()
    assert s["prefill_chunks"] == 3  # 40 tokens -> a 48-token bucket
    return [jsonl], [f"prefill chunk tokens: {s['prefill_chunk_tokens']}",
                     "prefill_stall_ms"]


def _drive_sessions(tmp_path):
    """Park a decoding stream to disk and resume it on the same engine."""
    from mamba_distributed_tpu.serving import DiskSessionStore, SessionStore
    from mamba_distributed_tpu.serving.service import wire

    cfg, params = _tiny_serving()
    jsonl = str(tmp_path / "s.jsonl")
    store = SessionStore(disk=DiskSessionStore(str(tmp_path / "park")))
    metrics = ServingMetrics(capacity=2, jsonl_path=jsonl)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2,
                        metrics=metrics, session_store=store)
    eng.submit(_request(4, 3, seed=1))
    rid = eng.submit(_request(6, 12))
    eng.step()
    eng.step()  # both are decoding
    request, snap = eng.park(rid)
    sid = store.park({"request": wire.encode_request_tree(request),
                      "snapshot": snap})
    payload = store.resume(sid)
    new_rid = eng.submit_migrated(
        wire.decode_request_tree(payload["request"]), payload["snapshot"])
    while eng.pending:
        eng.step()
    assert len(eng.results[new_rid].new_tokens) == 12
    ticks = [r for r in load_events([jsonl]) if r["kind"] == "serving_tick"]
    assert sum(t.get("session_parks", 0) for t in ticks) == 1
    se = metrics.summary()["sessions"]
    assert (se["parks"], se["resumes"]) == (1, 1)
    return [jsonl], ["sessions: ", "1 parks / 1 resumes / 0 expired"]


def _drive_kv_pages(tmp_path):
    """A hybrid engine: the page pool's gauges ride every tick."""
    cfg = dataclasses.replace(
        _tiny_chunked_serving()[0], attn_layer_idx=(1,), attn_num_heads=4,
        attn_num_kv_heads=2, remat=False, kv_page_tokens=8,
        kv_slot_tokens=64)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    jsonl = str(tmp_path / "s.jsonl")
    metrics = ServingMetrics(capacity=2, jsonl_path=jsonl)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2,
                        metrics=metrics)
    eng.run([_request(20, 4), _request(9, 4, seed=1)])
    assert metrics.peak_kv_pages_used > 0
    return [jsonl], [f"kv pages: peak {metrics.peak_kv_pages_used}/"
                     f"{metrics.kv_pages_capacity}"]


def _drive_preemptions(tmp_path):
    """A higher-priority arrival takes the one slot of a decoding stream."""
    cfg, params = _tiny_serving()
    jsonl = str(tmp_path / "s.jsonl")
    metrics = ServingMetrics(capacity=1, jsonl_path=jsonl)
    eng = ServingEngine(params, cfg, capacity=1, tokens_per_tick=2,
                        metrics=metrics)
    eng.submit(_request(9, 12, priority=0))
    eng.step()
    eng.step()  # the low-priority request is mid-decode
    eng.submit(_request(7, 4, seed=1, priority=5))
    while eng.pending:
        eng.step()
    assert metrics.preemptions == 1
    return [jsonl], ["preemptions: 1"]


def _drive_goodput(tmp_path):
    """Useful tokens against computed lanes, from the engine's own ticks."""
    cfg, params = _tiny_serving()
    jsonl = str(tmp_path / "s.jsonl")
    metrics = ServingMetrics(capacity=2, jsonl_path=jsonl)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2,
                        metrics=metrics)
    eng.run([_request(4 + i, 4, seed=i) for i in range(3)])
    g = metrics.summary()["goodput"]
    return [jsonl], [f"goodput: {g['useful_tokens']} useful tokens / "
                     f"{g['wasted_token_lanes']} wasted lanes",
                     "serving MFU: -"]  # no MFU off a TPU


def _drive_slo(tmp_path):
    """Targets no request can miss: the table counts every request met."""
    from mamba_distributed_tpu.obs import SLOMonitor

    cfg, params = _tiny_serving()
    jsonl, events = str(tmp_path / "s.jsonl"), str(tmp_path / "e.jsonl")
    tracer = SpanTracer(events)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2,
                        metrics=ServingMetrics(capacity=2, jsonl_path=jsonl),
                        tracer=tracer,
                        slo=SLOMonitor(ttft_p95_ms=1e9, window=4,
                                       tracer=tracer))
    eng.run([_request(4 + i, 3, seed=i) for i in range(3)])
    return [jsonl, events], ["== SLO attainment (rolling window 4) ==",
                             "100.0%"]


def _drive_tier_migrations(tmp_path):
    """Prefill and decode tiers: the long prompt is handed over once."""
    from mamba_distributed_tpu.serving import RequestRouter

    cfg, params = _tiny_chunked_serving()
    cfg = dataclasses.replace(cfg, disagg_prompt_threshold=16)
    jsonl = str(tmp_path / "s.jsonl")
    router = RequestRouter(params, cfg, num_replicas=2, capacity=2,
                           tokens_per_tick=2, roles=["prefill", "decode"],
                           jsonl_path=jsonl)
    router.run([_request(40, 3), _request(5, 3, seed=1)])
    assert router.migrations == 1
    return [jsonl], ["tier migrations: 1 prefill->decode handoff(s)",
                     "== migrations (disaggregated tiers) =="]


def _drive_pipeline(tmp_path):
    """Two stages over the forced host devices: the microbatched clock."""
    cfg, params = _tiny_serving()
    cfg = dataclasses.replace(cfg, serving_stage_shards=2)
    jsonl = str(tmp_path / "s.jsonl")
    metrics = ServingMetrics(capacity=4, jsonl_path=jsonl)
    eng = ServingEngine(params, cfg, capacity=4, tokens_per_tick=2,
                        metrics=metrics)
    eng.run([_request(4 + i, 4, seed=i) for i in range(4)])
    pipe = metrics.summary()["pipeline"]
    assert pipe["pipelined_ticks"] >= 1 and pipe["bubble_lanes"] > 0
    return [jsonl], [f"pipeline: 2 stages   {pipe['pipelined_ticks']}/"]


@pytest.mark.parametrize("drive", [
    _drive_prefill, _drive_sessions, _drive_kv_pages, _drive_preemptions,
    _drive_goodput, _drive_slo, _drive_tier_migrations, _drive_pipeline,
], ids=lambda drive: drive.__name__.removeprefix("_drive_"))
def test_report_renders_feature_block(drive, tmp_path, capsys):
    """The operator's path, feature by feature: engine or router ->
    ``ServingMetrics(jsonl_path=...)`` -> ``obs_report.py``'s ``main``
    prints the feature's block with the engine's own counts in it.  (The
    blocks of the prefix cache, adapters, speculation, compaction and
    quantisation are rendered by the tests of those features' files.)"""
    files, wanted = drive(tmp_path)
    capsys.readouterr()
    assert obs_report_main(files) == 0
    text = capsys.readouterr().out
    for line in wanted:
        assert line in text, f"{line!r} not in the report:\n{text}"


@pytest.mark.fast
def test_obs_report_survives_torn_lines(tmp_path):
    path = tmp_path / "torn.jsonl"
    path.write_text(
        json.dumps({"kind": "train", "step": 0, "loss": 2.0,
                    "step_ms": 10.0, "tokens_per_sec": 100.0}) + "\n"
        + '{"kind": "train", "step": 1, "lo'  # torn mid-write
    )
    report = build_report(load_events([str(path)]))
    assert report["train"]["steps"] == 1


# --------------------------------------- request-flow tracing (ISSUE 7)


@pytest.mark.fast
def test_trace_ids_unique_and_context():
    from mamba_distributed_tpu.obs import mint_trace_id

    ids = {mint_trace_id() for _ in range(100)}
    assert len(ids) == 100  # monotone counter under the process nonce


@pytest.mark.fast
def test_tracer_wall_clock_header(tmp_path):
    """Satellite: t_ms is a per-process perf_counter offset; the header
    record stamps the wall-clock epoch that makes streams mergeable."""
    import time

    path = str(tmp_path / "e.jsonl")
    before = time.time()
    t = SpanTracer(path)
    after = time.time()
    t.event("mark")
    header = load_events([path])[0]
    assert header["kind"] == "trace_header"
    assert before - 1e-3 <= header["wall_t0_s"] <= after + 1e-3
    assert header["pid"] == os.getpid()


@pytest.mark.fast
def test_tracer_stamps_per_thread_tids(tmp_path):
    """Spans from different host threads (async checkpoint vs trainer)
    overlap un-nested in wall time — each thread needs its own tid or
    the exported track holds invalid overlapping slices."""
    import threading

    path = str(tmp_path / "e.jsonl")
    t = SpanTracer(path)
    with t.span("main_phase"):
        th = threading.Thread(target=lambda: t.event("worker_mark"))
        th.start()
        th.join()
    recs = [r for r in load_events([path]) if r["kind"] != "trace_header"]
    tids = {r["name"]: r["tid"] for r in recs}
    assert tids["main_phase"] != tids["worker_mark"]
    assert sorted(tids.values()) == [0, 1]  # small stable indices


@pytest.mark.fast
def test_trace_ids_fork_safe():
    """A fork-spawned worker must reseed the process nonce: inheriting
    the parent's nonce+counter would mint colliding ids fabric-wide."""
    from mamba_distributed_tpu.obs import mint_trace_id

    if not hasattr(os, "fork"):
        pytest.skip("no fork on this platform")
    parent_id = mint_trace_id()
    r, w = os.pipe()
    with warnings.catch_warnings():
        # jax warns that fork + threads may deadlock; the child only
        # mints an id, writes a pipe and _exits — no locks touched
        warnings.simplefilter("ignore", RuntimeWarning)
        pid = os.fork()
    if pid == 0:  # child: mint under the reseeded nonce, report, exit
        os.write(w, mint_trace_id().encode())
        os._exit(0)
    os.close(w)
    child_id = os.read(r, 256).decode()
    os.close(r)
    os.waitpid(pid, 0)
    assert child_id and child_id != parent_id
    # nonce differs, not just the counter suffix
    assert child_id.rsplit("-", 1)[0] != parent_id.rsplit("-", 1)[0]


def test_engine_stamps_traces_and_goodput(tmp_path):
    """Acceptance pins: every request record carries trace_id, every
    serving_tick record carries useful_tokens / goodput_tokens_per_sec /
    serving_mfu plus the live trace-id set, and per-request spans carry
    the trace attr."""
    cfg, params = _tiny_serving()
    jsonl = str(tmp_path / "serving.jsonl")
    events = str(tmp_path / "events.jsonl")
    metrics = ServingMetrics(capacity=2, jsonl_path=jsonl)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2,
                        metrics=metrics, tracer=SpanTracer(events))
    eng.run([GenerationRequest(prompt_ids=np.ones(4 + i, np.int32),
                               max_new_tokens=4, key=jax.random.PRNGKey(i))
             for i in range(3)])
    recs = load_events([jsonl])
    reqs = [r for r in recs if r["kind"] == "request"]
    ticks = [r for r in recs if r["kind"] == "serving_tick"]
    traces = {r["trace_id"] for r in reqs}
    assert len(traces) == 3  # one trace per request journey
    seen_live = set()
    for t in ticks:
        assert t["useful_tokens"] >= 0
        assert t["wasted_token_lanes"] >= 0
        # lanes computed = capacity * tokens_per_tick (+ chunk lanes)
        assert t["useful_tokens"] + t["wasted_token_lanes"] >= 2 * 2
        assert t["goodput_tokens_per_sec"] is not None
        # MFU is a statement about a TPU; on the CPU the field is None
        assert "serving_mfu" in t and t["serving_mfu"] is None
        seen_live.update(t["traces"])
    assert seen_live == traces  # every request decoded under its trace
    total_emitted = sum(t["tokens_emitted"] for t in ticks)
    assert total_emitted == 12
    # ONE-SHOT prefills count toward goodput too (4+5+6 prompt tokens)
    # — useful work must be comparable across the chunking threshold
    assert sum(t["prefill_oneshot_tokens"] for t in ticks) == 15
    assert sum(t["useful_tokens"] for t in ticks) == total_emitted + 15
    # per-request spans in the tracer stream carry the trace attr
    spans = [e for e in load_events([events]) if e["kind"] == "span"]
    prefill_traces = {s["trace"] for s in spans
                      if s["name"] == "serving_prefill"}
    assert prefill_traces == traces
    g = metrics.summary()["goodput"]
    assert g["useful_tokens"] == 12 + 15
    assert g["goodput_tokens_per_sec"] > 0
    assert g["serving_mfu"] is None
    assert g["useful_fraction"] is not None and 0 < g["useful_fraction"] <= 1


def test_oneshot_only_config_prices_prefill_flops():
    """With chunking disabled (prefill_chunk_tokens=0, one-shot only)
    the prefill FLOPs rate must be priced at a representative prompt
    length, not seq_len=1.  (Hybrid engines — where the O(t) attention
    terms make the length matter most — reject chunking-disabled
    configs outright, so this pins the defensive non-hybrid path.)"""
    from mamba_distributed_tpu.utils.flops import flops_per_token

    cfg, params = _tiny_serving()
    cfg = dataclasses.replace(cfg, prefill_chunk_tokens=0)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2)
    expect = flops_per_token(cfg, 256, training=False, convention="model")
    assert eng.metrics._fpt_prefill == expect


def test_router_resubmission_mints_fresh_trace(tmp_path):
    """Submitting the SAME GenerationRequest object twice is two
    journeys: the router keeps the minted trace on its routing entry
    (so a failover re-placement continues it) without writing it back
    onto the caller's object — the second submission gets a new
    trace id, not a replay of the first one's."""
    cfg, params = _tiny_serving()
    from mamba_distributed_tpu.serving import RequestRouter

    jsonl = str(tmp_path / "serve.jsonl")
    router = RequestRouter(params, cfg, num_replicas=1, capacity=2,
                           tokens_per_tick=2, jsonl_path=jsonl)
    req = GenerationRequest(prompt_ids=np.ones(4, np.int32),
                            max_new_tokens=3, key=jax.random.PRNGKey(0))
    router.run([req])
    assert req.trace_id is None  # caller's object never mutated
    router.run([req])
    recs = [r for r in load_events([jsonl]) if r["kind"] == "request"]
    assert len(recs) == 2
    assert recs[0]["trace_id"] != recs[1]["trace_id"]


def _tiny_chunked_serving():
    """The tiny serving model with chunked prefill on — ONE shared
    shape for every chunk-path test in this file, so the tier-1 run
    compiles its chunk step/tick once."""
    cfg, params = _tiny_serving()
    cfg = dataclasses.replace(cfg, prefill_chunk_tokens=16,
                              prefill_tokens_per_tick=16)
    return cfg, params


def test_chunked_prefill_goodput_counts_padding_waste(tmp_path):
    """Chunk padding is waste: a prompt that left-pads inside chunk 0
    contributes chunk-minus-real wasted lanes to the tick stream."""
    cfg, params = _tiny_chunked_serving()
    jsonl = str(tmp_path / "serving.jsonl")
    metrics = ServingMetrics(capacity=2, jsonl_path=jsonl)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2,
                        metrics=metrics)
    # 40-token prompt -> 48-token bucket (3 chunks), 8 pad lanes
    eng.run([GenerationRequest(prompt_ids=np.arange(40, dtype=np.int32) % 7,
                               max_new_tokens=3,
                               key=jax.random.PRNGKey(0))])
    ticks = [r for r in load_events([jsonl])
             if r["kind"] == "serving_tick"]
    assert sum(t["prefill_chunk_tokens"] for t in ticks) == 48
    real = sum(t["useful_tokens"] - t["tokens_emitted"] for t in ticks)
    assert real == 40  # non-pad prompt tokens counted useful
    assert metrics.summary()["goodput"]["useful_tokens"] == 40 + 3


# ------------------------------------------------------------ SLO monitor


@pytest.mark.fast
def test_slo_monitor_breach_and_recovery(tmp_path):
    from mamba_distributed_tpu.obs import SLOMonitor

    tracer = SpanTracer(str(tmp_path / "e.jsonl"))
    mon = SLOMonitor(ttft_p95_ms=100.0, window=4, tracer=tracer)

    def req(ttft):
        return {"ttft_ms": ttft, "queue_wait_ms": 1.0}

    for _ in range(4):
        mon.observe_request(req(50.0))
    assert mon.breaches["ttft_ms"] == 0
    for _ in range(4):  # window fills with breaching samples
        mon.observe_request(req(500.0))
    assert mon.breaches["ttft_ms"] == 1  # ONE transition, not 4 alarms
    for _ in range(4):  # recover
        mon.observe_request(req(10.0))
    ev = [e for e in load_events([str(tmp_path / "e.jsonl")])
          if e["kind"] == "event"]
    names = [e["name"] for e in ev]
    assert names.count("slo_breach") == 1
    assert names.count("slo_recovered") == 1
    assert names[0] == "slo_config"  # targets stamped into the stream
    s = mon.summary()["metrics"]["ttft_ms"]
    assert s["requests"] == 12 and s["met"] == 8
    assert s["attainment"] == pytest.approx(8 / 12, abs=1e-4)
    assert not s["in_breach"]


@pytest.mark.fast
def test_slo_monitor_itl_uses_request_histogram():
    from mamba_distributed_tpu.obs import SLOMonitor

    mon = SLOMonitor(itl_p95_ms=20.0, window=8)
    h = StreamingHistogram()
    for v in [5.0] * 19 + [100.0]:  # p95 == 5ms -> meets target
        h.record(v)
    mon.observe_request({"itl_hist": h.to_dict()})
    mon.observe_request({"itl_hist": None})  # 1-token request: no ITL
    s = mon.summary()["metrics"]["itl_ms"]
    assert s["requests"] == 1 and s["met"] == 1


@pytest.mark.fast
def test_slo_config_knobs_validate():
    from mamba_distributed_tpu.obs import SLOMonitor

    with pytest.raises(ValueError, match=">= 0"):
        TelemetryConfig(slo_ttft_p95_ms=-1.0)
    with pytest.raises(ValueError, match="slo_window_requests"):
        TelemetryConfig(slo_window_requests=0)
    with pytest.raises(ValueError, match="window"):
        SLOMonitor(ttft_p95_ms=1.0, window=0)
    # from_config: None when nothing is targeted, a live monitor else
    assert SLOMonitor.from_config(TelemetryConfig()) is None
    mon = SLOMonitor.from_config(
        TelemetryConfig(slo_ttft_p95_ms=50.0, slo_window_requests=16)
    )
    assert mon is not None and mon.window == 16
    assert mon.targets == {"ttft_ms": 50.0}


# --------------------------------------------- trace export (tentpole)


@pytest.mark.fast
def test_chrome_trace_aligns_streams_on_wall_clock():
    """Two streams whose t_ms offsets overlap but whose wall epochs
    differ must land disjoint on the merged timeline."""
    from mamba_distributed_tpu.obs import to_chrome_trace

    a = [{"kind": "trace_header", "wall_t0_s": 100.0, "pid": 1},
         {"kind": "span", "name": "x", "t_ms": 10.0, "dur_ms": 5.0}]
    b = [{"kind": "trace_header", "wall_t0_s": 200.0, "pid": 2},
         {"kind": "span", "name": "y", "t_ms": 10.0, "dur_ms": 5.0}]
    doc = to_chrome_trace([a, b], labels=["a", "b"])
    spans = {e["name"]: e for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert spans["x"]["ts"] == pytest.approx(100.0 * 1e6 + 10_000)
    assert spans["y"]["ts"] == pytest.approx(200.0 * 1e6 + 10_000)
    assert spans["x"]["pid"] != spans["y"]["pid"]
    assert doc["metadata"]["unaligned_streams"] == 0
    # headerless stream: exported, but counted unaligned
    doc2 = to_chrome_trace([[{"kind": "span", "name": "z", "t_ms": 1.0,
                              "dur_ms": 1.0}]])
    assert doc2["metadata"]["unaligned_streams"] == 1


def test_trace_export_flow_links_router_to_replica(tmp_path):
    """Acceptance criterion: one command turns a 2-replica router run's
    streams into a single Perfetto-loadable trace in which a request's
    spans are flow-linked across router -> replica -> engine — verified
    by parsing the trace-event JSON."""
    cfg, params = _tiny_serving()
    from mamba_distributed_tpu.serving import RequestRouter

    paths = [str(tmp_path / n)
             for n in ("router.jsonl", "rep0.jsonl", "rep1.jsonl")]
    router = RequestRouter(
        params, cfg, num_replicas=2, capacity=2, tokens_per_tick=2,
        jsonl_path=str(tmp_path / "serve.jsonl"),
        tracer=SpanTracer(paths[0]),
        replica_tracers=[SpanTracer(paths[1]), SpanTracer(paths[2])],
    )
    router.run([GenerationRequest(prompt_ids=np.ones(4 + i, np.int32),
                                  max_new_tokens=4,
                                  key=jax.random.PRNGKey(i))
                for i in range(4)])
    out = str(tmp_path / "trace.json")
    # the one command from the acceptance criterion
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "trace_export.py"),
         *paths, "-o", out],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    doc = json.load(open(out))
    events = doc["traceEvents"]
    # three process tracks, named after the streams
    names = {e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert names == {"router.jsonl", "rep0.jsonl", "rep1.jsonl"}
    # every request's flow chain starts on the ROUTER track (pid 0,
    # the serving_route span) and finishes on a REPLICA track
    flows = [e for e in events if e.get("cat") == "request"]
    # flow ids are the trace ids themselves (strings) — hashing to an
    # int would reintroduce cross-linking collisions
    assert all(isinstance(f["id"], str) for f in flows)
    by_id: dict = {}
    for f in flows:
        by_id.setdefault(f["id"], []).append(f)
    assert len(by_id) == 4  # all four requests linked
    for chain in by_id.values():
        chain.sort(key=lambda e: e["ts"])
        assert chain[0]["ph"] == "s" and chain[0]["pid"] == 0
        assert chain[-1]["ph"] == "f" and chain[-1]["pid"] in (1, 2)
        # arrows bind inside real slices on their tracks
        for f in chain:
            assert any(
                e.get("ph") == "X" and e["pid"] == f["pid"]
                and e["ts"] <= f["ts"] <= e["ts"] + e["dur"]
                for e in events
            )
    assert doc["metadata"]["unaligned_streams"] == 0
    assert "4 flow-linked request(s)" in p.stdout


def test_router_full_telemetry_zero_extra_traces(tmp_path):
    """Satellite (acceptance pin, fabric half): a multi-replica router
    serve() with trace propagation, goodput accounting and the SLO
    monitor ALL enabled adds zero jit compilations over the bare run —
    the whole PR-7 surface stays host-side."""
    from mamba_distributed_tpu.obs import SLOMonitor
    from mamba_distributed_tpu.serving import RequestRouter
    from mamba_distributed_tpu.serving.engine import (
        TRACE_COUNTS as ENGINE_TRACES,
    )
    from mamba_distributed_tpu.serving.prefill import (
        TRACE_COUNTS as CHUNK_TRACES,
    )

    cfg, params = _tiny_chunked_serving()

    def reqs():
        # short mix plus one chunked long prompt, so the chunk step is
        # on the traced surface too
        out = [GenerationRequest(prompt_ids=np.ones(4, np.int32),
                                 max_new_tokens=3,
                                 key=jax.random.PRNGKey(i))
               for i in range(3)]
        out.append(GenerationRequest(
            prompt_ids=np.arange(20, dtype=np.int32) % 5,
            max_new_tokens=3, key=jax.random.PRNGKey(9)))
        return out

    kw = dict(num_replicas=2, capacity=2, tokens_per_tick=2)
    RequestRouter(params, cfg, **kw).run(reqs())
    base = dict(ENGINE_TRACES), dict(CHUNK_TRACES)

    tracer = SpanTracer(str(tmp_path / "events.jsonl"))
    slo = SLOMonitor(ttft_p95_ms=0.001, queue_wait_p95_ms=1000.0,
                     itl_p95_ms=1000.0, window=4, tracer=tracer)
    router = RequestRouter(
        params, cfg, jsonl_path=str(tmp_path / "serve.jsonl"),
        tracer=tracer, slo=slo, **kw,
    )
    consumed = sum(1 for _ in router.serve(reqs()))
    assert consumed == 12
    assert (dict(ENGINE_TRACES), dict(CHUNK_TRACES)) == base
    # the full surface actually ran: goodput on every tick, traces
    # propagated, SLO breach recorded
    recs = load_events([str(tmp_path / "serve.jsonl")])
    ticks = [r for r in recs if r["kind"] == "serving_tick"]
    assert ticks and all("serving_mfu" in t and "traces" in t
                         for t in ticks)
    req_recs = [r for r in recs if r["kind"] == "request"]
    assert len({r["trace_id"] for r in req_recs}) == 4
    assert mon_breached(slo)


def mon_breached(slo) -> bool:
    return any(m["breaches"] for m in slo.summary()["metrics"].values())


# ------------------------------------- obs_report: SLO/goodput/replicas


@pytest.mark.fast
def test_obs_report_merges_replica_itl_histograms():
    """Satellite: per-replica request records merge into per-replica
    AND fabric-wide ITL views — exercised on histograms with disjoint
    and overlapping bucket sets."""

    def req(rid, replica, values):
        h = StreamingHistogram()
        for v in values:
            h.record(v)
        return {"kind": "request", "request_id": rid, "replica": replica,
                "new_tokens": len(values) + 1, "finish_reason": "length",
                "queue_wait_ms": 1.0, "ttft_ms": 2.0, "e2e_ms": 3.0,
                "itl_hist": h.to_dict()}

    def tick(replica):
        return {"kind": "serving_tick", "tick": 1, "occupied": 1,
                "capacity": 2, "replica": replica, "queue_depth": 0,
                "tokens_emitted": 2, "tick_ms": 10.0}

    # replica 0: ~10ms, replica 1: ~10s — DISJOINT buckets; the two
    # replica-0 requests overlap each other's buckets exactly
    events = [tick(0), tick(1),
              req(0, 0, [10.0] * 8), req(1, 0, [12.0] * 8),
              req(2, 1, [10_000.0] * 8)]
    rep = build_report(events)
    r0 = rep["replicas"][0]["itl_ms"]
    r1 = rep["replicas"][1]["itl_ms"]
    fab = rep["fabric"]["itl_ms"]
    assert r0["count"] == 16 and r1["count"] == 8
    assert fab["count"] == 24
    g = StreamingHistogram().growth
    assert 10.0 / g <= r0["p50"] <= 12.0 * g
    assert 10_000.0 / g <= r1["p50"] <= 10_000.0 * g
    # fabric merge == one histogram fed the combined stream
    both = StreamingHistogram()
    for v in [10.0] * 8 + [12.0] * 8 + [10_000.0] * 8:
        both.record(v)
    for q in ("p50", "p95", "p99"):
        assert fab[q] == both.summary()[q]
    # the merged view is visibly worse than replica 0's own p95 —
    # exactly what the per-replica split exists to show
    assert fab["p99"] > r0["p99"]
    text = format_report(rep)
    assert "itl_p50/p95" in text and "all" in text


@pytest.mark.fast
def test_obs_report_slo_and_goodput_sections():
    events = [
        {"kind": "event", "name": "slo_config", "t_ms": 0.0, "window": 8,
         "ttft_ms_p95_target": 100.0, "queue_wait_ms_p95_target": 50.0},
        {"kind": "event", "name": "slo_breach", "t_ms": 5.0,
         "metric": "ttft_ms", "target": 100.0, "p95": 300.0, "window": 8},
    ]
    for i in range(10):
        events.append({"kind": "request", "request_id": i,
                       "prompt_tokens": 4, "new_tokens": 4,
                       "finish_reason": "length",
                       "queue_wait_ms": 10.0,
                       "ttft_ms": 50.0 if i < 7 else 500.0,
                       "e2e_ms": 600.0})
        events.append({"kind": "serving_tick", "tick": i + 1,
                       "occupied": 2, "capacity": 4, "queue_depth": 0,
                       "tokens_emitted": 4, "tick_ms": 100.0,
                       "prefill_stall_ms": 0.0, "useful_tokens": 4,
                       "wasted_token_lanes": 12,
                       "goodput_tokens_per_sec": 40.0,
                       "serving_mfu": 0.25})
    rep = build_report(events)
    slo = rep["slo"]
    assert slo["window"] == 8
    assert slo["metrics"]["ttft_ms"]["attainment"] == 0.7
    assert slo["metrics"]["ttft_ms"]["breaches"] == 1
    assert slo["metrics"]["queue_wait_ms"]["attainment"] == 1.0
    assert "itl_ms" not in slo["metrics"]  # untargeted
    g = rep["serving"]["goodput"]
    assert g["useful_tokens"] == 40 and g["wasted_token_lanes"] == 120
    assert g["useful_fraction"] == 0.25
    assert g["goodput_tokens_per_sec"] == 40.0
    assert g["serving_mfu"] == 0.25
    text = format_report(rep)
    assert "SLO attainment" in text and "70.0%" in text
    assert "goodput" in text and "serving MFU: 25.00%" in text


@pytest.mark.fast
def test_obs_report_train_and_span_sections(tmp_path):
    """MetricsLogger's metrics.jsonl is directly ingestible."""
    from mamba_distributed_tpu.utils.metrics import MetricsLogger

    logger = MetricsLogger(str(tmp_path))
    logger.train_step(0, 2.5, 1e-4, 0.9, 0.1, 1000.0, 0.1)
    logger.train_step(1, float("nan"), 1e-4, 0.9, 0.1, 1000.0, 0.1)
    logger.val(1, 2.4)
    report = build_report(load_events([str(tmp_path / "metrics.jsonl")]))
    assert report["train"]["steps"] == 2
    assert report["train"]["non_finite_losses"] == 1
    assert report["val"]["last_loss"] == 2.4
