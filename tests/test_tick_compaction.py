"""Occupancy-adaptive compacted ticks (serving/engine.py; ISSUE 14).

The contract under test:

  * PARITY — with ``cfg.tick_compaction`` on, every engine token stream
    is BIT-identical to the compaction-off engine (and therefore to
    solo ``generate()``, whose parity the off engine pins): mamba1,
    mamba2, the hybrid paged config with chunked longs, speculative
    K>0 ticks, prefix-cache warm hits, preempt/resume, disaggregated
    migration, and the (2,2) serving mesh.  Compaction gathers the
    live slots into a pow2 lane bucket, runs the IDENTICAL tick jit at
    bucket width, and scatters back — same per-row math, fewer pad
    rows.
  * BUCKETS — the lane bucket grows immediately with live slots and
    shrinks only after ``cfg.compaction_hysteresis_ticks`` consecutive
    smaller-sufficient ticks (no recompile thrash at a pow2 boundary);
    one gather/tick/scatter trace per distinct bucket width, flat on a
    repeat run.
  * HONESTY — tick records bill ``slot_lanes`` (and therefore the
    goodput ``wasted_token_lanes``) at the compacted width, stamp
    ``compaction_width``, and ``summary()["compaction"]`` reports the
    bucket histogram / recompiles / lanes saved; obs_report.py renders
    the "compaction:" line.
  * OFF-BY-DEFAULT — ``tick_compaction=False`` is byte-stable: no
    gather/scatter traces, no record stamps, summary block None.

Runnable standalone: ``pytest -m compaction``.  (The heaviest parity
matrices are marked ``slow``.)
"""

import dataclasses
import json
import os
import sys

import jax
import numpy as np
import pytest

from mamba_distributed_tpu.config import ModelConfig
from mamba_distributed_tpu.models import init_lm_params
from mamba_distributed_tpu.serving import (
    GenerationRequest,
    RequestRouter,
    ServingEngine,
)
from mamba_distributed_tpu.serving import state_cache
from mamba_distributed_tpu.serving.engine import (
    TRACE_COUNTS as ENGINE_TRACES,
)

pytestmark = [pytest.mark.serving, pytest.mark.compaction]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHUNK = 16


def tiny_cfg(layer="mamba2", **kw):
    return ModelConfig(d_model=32, n_layer=2, vocab_size=64, ssm_layer=layer,
                       headdim=8, chunk_size=16, d_state=16,
                       compute_dtype="float32", **kw)


def hybrid_cfg(**kw):
    kw.setdefault("prefill_chunk_tokens", CHUNK)
    kw.setdefault("prefill_tokens_per_tick", CHUNK)
    return tiny_cfg(attn_layer_idx=(1,), attn_num_heads=4,
                    attn_num_kv_heads=2, remat=False, kv_page_tokens=8,
                    kv_slot_tokens=128, **kw)


def mixed_requests(n=4, seed=0, vocab=64, max_new=(6, 20), long_len=None):
    """Deterministic mixed-length workload; optionally one chunked-long
    prompt so the prefill path rides along."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(5, 30))
        if long_len is not None and i == 1:
            plen = long_len
        reqs.append(GenerationRequest(
            prompt_ids=rng.integers(0, vocab, size=plen).astype(np.int32),
            max_new_tokens=int(rng.integers(*max_new)),
            seed=100 + i,
        ))
    return reqs


def streams(results):
    return [r.new_tokens.tolist() for r in results]


def run_pair(params, cfg, make_reqs, capacity=8, **engine_kw):
    """(compaction off, compaction on) engine streams for one
    workload; the pair must be bit-identical."""
    off = ServingEngine(params, cfg, capacity=capacity,
                        **engine_kw).run(make_reqs())
    ccfg = dataclasses.replace(cfg, tick_compaction=True)
    eng = ServingEngine(params, ccfg, capacity=capacity, **engine_kw)
    on = eng.run(make_reqs())
    return streams(off), streams(on), eng


# ------------------------------------------------------------------ parity


@pytest.mark.fast
@pytest.mark.parametrize("layer", ["mamba2", "mamba1"])
def test_compaction_parity(layer):
    """Compacted == uncompacted, token for token, across a mixed
    workload whose occupancy spans several pow2 buckets."""
    cfg = tiny_cfg(layer)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    off, on, eng = run_pair(params, cfg, lambda: mixed_requests(4))
    assert on == off
    comp = eng.metrics.summary()["compaction"]
    assert comp["ticks_compacted"] > 0
    assert comp["lanes_saved"] > 0


def test_compaction_parity_hybrid_chunked_long():
    """Hybrid paged KV + a chunked long prompt: the compacted tick's
    page-table slice covers live lanes only, pad lanes point at the
    trash page, and streams stay bit-identical."""
    cfg = hybrid_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    off, on, eng = run_pair(
        params, cfg, lambda: mixed_requests(4, long_len=40), capacity=4
    )
    assert on == off
    # page accounting survived compaction: everything recycled
    assert eng.page_pool.pages_in_use == 0


@pytest.mark.fast
def test_compaction_parity_spec():
    """Speculative K>0: the verify/commit launches compact the same way
    (lane-indexed feeds, per-lane advance) and the greedy streams stay
    token-identical — speculation is lossless, compacted or not."""
    cfg = tiny_cfg(spec_tokens=3)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    pat = rng.integers(0, 64, size=4).astype(np.int32)

    def reqs():
        return [GenerationRequest(prompt_ids=np.tile(pat, 4),
                                  max_new_tokens=18, top_k=1, seed=7 + i)
                for i in range(3)]

    off, on, eng = run_pair(params, cfg, reqs)
    assert on == off
    assert eng.metrics.summary()["compaction"]["ticks_compacted"] > 0


def test_compaction_parity_prefix_warm():
    """Prefix-cache warm hits (full + partial) on a compacted engine:
    admission seeds from snapshots exactly as before — compaction is
    tick-internal — and warm streams match the cache-off baseline."""
    cfg = tiny_cfg(prefill_chunk_tokens=CHUNK,
                   prefill_tokens_per_tick=CHUNK,
                   prefix_cache_entries=64)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    preamble = np.arange(1, 1 + 2 * CHUNK, dtype=np.int32) % 64

    def reqs():
        return [GenerationRequest(
            prompt_ids=np.concatenate(
                [preamble, np.full((4,), 3 + i, np.int32)]),
            max_new_tokens=10, seed=50 + i) for i in range(3)]

    off_cfg = dataclasses.replace(cfg, prefix_cache_entries=0)
    baseline = streams(ServingEngine(params, off_cfg, capacity=4).run(reqs()))
    ccfg = dataclasses.replace(cfg, tick_compaction=True)
    eng = ServingEngine(params, ccfg, capacity=4)
    cold = streams(eng.run(reqs()))  # populates the cache
    warm = streams(eng.run(reqs()))  # full hits, compacted ticks
    assert cold == baseline
    assert warm == baseline
    assert eng.metrics.prefix_full_hits > 0


@pytest.mark.fast
def test_compaction_preempt_resume_parity():
    """A priority preemption mid-stream on a compacted engine: swap-out
    and restore operate on the full pool between ticks, so the resumed
    stream continues bit-exactly — compared against the compaction-off
    engine running the identical priority workload."""
    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)

    def drive(run_cfg):
        eng = ServingEngine(params, run_cfg, capacity=1,
                            tokens_per_tick=2)
        lo = GenerationRequest(prompt_ids=np.arange(1, 9, dtype=np.int32),
                               max_new_tokens=16, seed=1)
        hi = GenerationRequest(prompt_ids=np.arange(2, 10, dtype=np.int32),
                               max_new_tokens=6, seed=2, priority=5)
        i_lo = eng.submit(lo)
        for _ in range(2):
            eng.step()
        i_hi = eng.submit(hi)
        while eng.pending:
            eng.step()
        return (eng.results[i_lo].new_tokens.tolist(),
                eng.results[i_hi].new_tokens.tolist(), eng)

    off_lo, off_hi, off_eng = drive(cfg)
    on_lo, on_hi, on_eng = drive(
        dataclasses.replace(cfg, tick_compaction=True))
    assert off_eng.metrics.preemptions >= 1
    assert on_eng.metrics.preemptions >= 1
    assert on_lo == off_lo
    assert on_hi == off_hi


@pytest.mark.slow
def test_compaction_migration_parity():
    """Disaggregated prefill->decode migration with compaction on at
    BOTH tiers: the artifact restore lands in the full pool and the
    compacted decode ticks continue it bit-exactly."""
    cfg = tiny_cfg(prefill_chunk_tokens=CHUNK,
                   prefill_tokens_per_tick=CHUNK,
                   disagg_prompt_threshold=24)

    params = init_lm_params(jax.random.PRNGKey(0), cfg)

    def run(router_cfg):
        return RequestRouter(
            params, router_cfg, num_replicas=2, capacity=4,
            roles=["prefill", "decode"],
        ).run(mixed_requests(3, long_len=48))

    off = streams(run(cfg))
    on = streams(run(dataclasses.replace(cfg, tick_compaction=True)))
    assert on == off


@pytest.mark.slow
def test_compaction_parity_tp_mesh():
    """(data=2, model=2) serving mesh: compact lanes keep the data-axis
    tiling (shard-local gathers, bucket a multiple of the shard count)
    and streams stay bit-identical to the uncompacted 2-D engine."""
    cfg = tiny_cfg(prefill_chunk_tokens=CHUNK,
                   prefill_tokens_per_tick=CHUNK,
                   serving_data_shards=2, serving_model_shards=2)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    off, on, eng = run_pair(
        params, cfg, lambda: mixed_requests(4, long_len=40)
    )
    assert on == off
    assert dict(eng.mesh.shape) == {"data": 2, "model": 2}
    # every compacted width tiles over both data shards
    comp = eng.metrics.summary()["compaction"]
    assert all(int(w) % 2 == 0 for w in comp["bucket_histogram"])


# ----------------------------------------------------- buckets + hysteresis


@pytest.mark.fast
def test_bucket_grows_immediately_shrinks_with_hysteresis():
    """The lane bucket must cover the live slots the moment they exist
    (growth can't lag a tick — the gather would drop a stream) but
    holds through ``compaction_hysteresis_ticks`` of lower occupancy
    before shrinking, so jitter around a pow2 edge doesn't thrash
    recompiles."""
    cfg = tiny_cfg(tick_compaction=True, compaction_hysteresis_ticks=3)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, capacity=8)
    # one long-budget request -> bucket 1
    eng.submit(GenerationRequest(prompt_ids=np.arange(1, 9, dtype=np.int32),
                                 max_new_tokens=40, seed=1))
    eng.step()
    assert eng._compact_bucket == 1
    # two more live slots -> need 4: growth is immediate
    for i in range(2):
        eng.submit(GenerationRequest(
            prompt_ids=np.arange(2, 10, dtype=np.int32),
            max_new_tokens=2, seed=2 + i))
    eng.step()
    assert eng._compact_bucket == 4
    # the short requests finish; the bucket holds for hysteresis ticks
    widths = []
    while eng.pending:
        eng.step()
        widths.append(eng._compact_bucket)
    assert widths[:2] == [4, 4], widths  # held (streak 1, 2)
    assert 1 in widths  # ...then shrank back down
    # and the stream still matches the uncompacted engine
    off = ServingEngine(params, dataclasses.replace(
        cfg, tick_compaction=False), capacity=8)
    got = off.run([GenerationRequest(
        prompt_ids=np.arange(1, 9, dtype=np.int32), max_new_tokens=40, seed=1)])
    assert eng.results[0].new_tokens.tolist() == \
        got[0].new_tokens.tolist()


@pytest.mark.fast
def test_per_bucket_trace_pins():
    """One gather/scatter/tick trace per distinct bucket width, and a
    repeat run at the same occupancy mix adds ZERO traces — the pow2
    discipline the prompt buckets established, extended to lanes."""
    cfg = tiny_cfg(tick_compaction=True, compaction_hysteresis_ticks=0)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)

    def run_once():
        eng = ServingEngine(params, cfg, capacity=8)
        eng.run(mixed_requests(5, seed=3))
        return eng

    eng = run_once()
    widths = {int(w) for w in
              eng.metrics.summary()["compaction"]["bucket_histogram"]
              if int(w) < 8}
    g0 = dict(state_cache.TRACE_COUNTS)
    t0 = ENGINE_TRACES["tick"]
    run_once()
    assert state_cache.TRACE_COUNTS == g0  # flat on the repeat
    assert ENGINE_TRACES["tick"] == t0
    # the first engine's distinct widths each compiled one trio at most
    assert g0["gather"] >= len(widths)
    assert g0["gather"] == g0["scatter"]


# -------------------------------------------------- honesty + byte-stability


@pytest.mark.fast
def test_off_by_default_byte_stable(tmp_path):
    """tick_compaction=False (the default) must leave records and
    traces untouched: no gather/scatter compiles, no compaction_width
    stamps, summary block None."""
    cfg = tiny_cfg()
    assert cfg.tick_compaction is False
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    g0 = dict(state_cache.TRACE_COUNTS)
    jsonl = str(tmp_path / "off.jsonl")
    from mamba_distributed_tpu.utils.metrics import ServingMetrics

    metrics = ServingMetrics(4, jsonl_path=jsonl)
    ServingEngine(params, cfg, capacity=4,
                  metrics=metrics).run(mixed_requests(3))
    assert state_cache.TRACE_COUNTS == g0
    assert metrics.summary()["compaction"] is None
    for ln in open(jsonl):
        assert "compaction_width" not in json.loads(ln)


@pytest.mark.fast
def test_goodput_bills_compacted_lanes(tmp_path):
    """Tick records price slot_lanes at the compacted width: at one
    live slot in an 8-slot pool the wasted token lanes collapse from
    ~capacity*steps to ~bucket*steps, and the compaction stamps ride
    the records (histogram + lanes_saved in summary())."""
    cfg = tiny_cfg(tick_compaction=True)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    from mamba_distributed_tpu.utils.metrics import ServingMetrics

    jsonl = str(tmp_path / "on.jsonl")
    metrics = ServingMetrics(8, jsonl_path=jsonl)
    eng = ServingEngine(params, cfg, capacity=8, metrics=metrics,
                        tokens_per_tick=4)
    eng.run([GenerationRequest(prompt_ids=np.arange(1, 9, dtype=np.int32),
                               max_new_tokens=12, seed=1)])
    ticks = [json.loads(ln) for ln in open(jsonl)
             if json.loads(ln).get("kind") == "serving_tick"]
    assert ticks
    for t in ticks:
        assert t["compaction_width"] == 1  # one live slot -> one lane
    # lanes billed at the bucket: in a prefill-free window the bill is
    # 1 lane * 4 sub-steps exactly (a full-width tick would bill 32)
    steady = [t for t in ticks if not t.get("prefill_oneshot_tokens")
              and not t.get("prefill_chunk_tokens")]
    assert steady
    for t in steady:
        assert t["useful_tokens"] + t["wasted_token_lanes"] == 4
    comp = metrics.summary()["compaction"]
    assert comp["bucket_histogram"] == {"1": len(ticks)}
    assert comp["lanes_saved"] == len(ticks) * (8 - 1) * 4
    assert comp["recompiles"] == 1


@pytest.mark.fast
def test_spec_lanes_billed_at_bucket(tmp_path):
    """Speculative ticks price capacity*(K+1) lanes uncompacted; with
    compaction on the same records bill bucket*(K+1) — rejected drafts
    still land in wasted_token_lanes, idle slots no longer do."""
    cfg = tiny_cfg(spec_tokens=3, tick_compaction=True)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    from mamba_distributed_tpu.utils.metrics import ServingMetrics

    jsonl = str(tmp_path / "spec.jsonl")
    metrics = ServingMetrics(8, jsonl_path=jsonl)
    eng = ServingEngine(params, cfg, capacity=8, metrics=metrics)
    eng.run([GenerationRequest(prompt_ids=np.tile(
        np.arange(1, 5, dtype=np.int32), 4), max_new_tokens=12, top_k=1,
        seed=1)])
    ticks = [json.loads(ln) for ln in open(jsonl)
             if json.loads(ln).get("kind") == "serving_tick"]
    assert ticks
    for t in ticks:
        assert t["compaction_width"] == 1
        assert t["spec_streams"] == 1
    # one lane * W=4 verify positions is the whole lane bill in a
    # prefill-free window (a launch can COMMIT up to W+1 tokens, so
    # useful may exceed the bill — wasted clamps at zero, never the
    # full-width capacity*(K+1)=32 a static tick would charge)
    steady = [t for t in ticks if not t.get("prefill_oneshot_tokens")
              and not t.get("prefill_chunk_tokens")]
    assert steady
    for t in steady:
        assert t["wasted_token_lanes"] <= 4


@pytest.mark.fast
def test_obs_report_renders_compaction_line(tmp_path):
    """The jsonl stream's compaction stamps surface as the report's
    "compaction:" line."""
    cfg = tiny_cfg(tick_compaction=True)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    from mamba_distributed_tpu.utils.metrics import ServingMetrics

    jsonl = str(tmp_path / "rep.jsonl")
    metrics = ServingMetrics(8, jsonl_path=jsonl)
    ServingEngine(params, cfg, capacity=8,
                  metrics=metrics).run(mixed_requests(2))
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    report = obs_report.build_report(obs_report.load_events([jsonl]))
    comp = report["serving"]["compaction"]
    assert comp["ticks_compacted"] > 0
    assert comp["min_width"] < 8
    text = obs_report.format_report(report)
    assert "compaction:" in text
