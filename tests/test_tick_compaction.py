"""The decode tick's lane ladder (serving/engine.py; ISSUE 14, one path
since ISSUE 34).

The contract under test:

  * PARITY — an engine that narrows its ticks yields token streams
    BIT-identical to one whose capacity is its only rung (and so to solo
    ``generate()``, whose parity that engine pins): mamba1, mamba2, the
    hybrid paged config with chunked longs, speculative K>0 ticks,
    prefix-cache warm hits, preempt/resume, disaggregated migration, and
    the (2,2) serving mesh.  A narrow tick gathers the live slots into a
    rung of lanes, advances them and writes them back inside the one tick
    program — same per-row math, fewer pad rows.
  * LADDER — the rungs depend on the capacity and the shard count alone
    (floor ``RUNG_FLOOR_LANES`` a shard, doubling, then the capacity; the
    tests that want rungs of 1, 2, 4 lower the floor); the rung in use is
    taken wider at once and narrower only after ``RUNG_HYSTERESIS_TICKS``
    consecutive narrower-sufficient ticks; the first tick at a launch shape
    runs every other rung once, so a run that later visits every rung
    traces nothing.
  * IN PLACE — the write-back is a row write into the donated pool, a
    loop over the kept lanes: no ``select_n`` and no gather of a pool
    leaf's shape.
  * HONESTY — tick records bill ``slot_lanes`` (and therefore the goodput
    ``wasted_token_lanes``) at the launched width and stamp
    ``compaction_width`` on every tick, the ``serving_tick`` span carries
    ``width`` >= ``live``, ``summary()["compaction"]`` reports the width
    histogram / programs / lanes saved, and obs_report.py renders the
    "compaction:" line.

Runnable standalone: ``pytest -m compaction``.  (The heaviest parity
matrices are marked ``slow``.)
"""

import contextlib
import dataclasses
import json
import os
import sys

import jax
import numpy as np
import pytest

from mamba_distributed_tpu.config import ModelConfig
from mamba_distributed_tpu.inference.generate import generate
from mamba_distributed_tpu.models import init_lm_params
from mamba_distributed_tpu.serving import (
    GenerationRequest,
    RequestRouter,
    ServingEngine,
)
from mamba_distributed_tpu.serving import engine as engine_mod
from mamba_distributed_tpu.serving import state_cache
from mamba_distributed_tpu.serving.engine import (
    TRACE_COUNTS as ENGINE_TRACES,
    tick_rungs,
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


@contextlib.contextmanager
def rung_floor(lanes):
    """Engines BUILT inside see a ladder that starts at ``lanes`` a shard
    (an engine reads the floor once, when it is built)."""
    old = engine_mod.RUNG_FLOOR_LANES
    engine_mod.RUNG_FLOOR_LANES = lanes
    try:
        yield
    finally:
        engine_mod.RUNG_FLOOR_LANES = old


def ladder_engine(params, cfg, floor=1, **engine_kw):
    """An engine whose ladder starts at ``floor`` lanes: at the tests' tiny
    capacities the shipped floor of 8 leaves one rung."""
    with rung_floor(floor):
        return ServingEngine(params, cfg, **engine_kw)


def run_pair(params, cfg, make_reqs, capacity=8, **engine_kw):
    """(one-rung engine, narrowing engine) streams for one workload; the
    pair must be bit-identical."""
    one = ServingEngine(params, cfg, capacity=capacity, **engine_kw)
    assert one._rungs == (capacity,)  # its capacity is its only rung
    off = one.run(make_reqs())
    eng = ladder_engine(params, cfg, capacity=capacity, **engine_kw)
    assert len(eng._rungs) > 1
    on = eng.run(make_reqs())
    return streams(off), streams(on), eng


# ------------------------------------------------------------------ parity


@pytest.mark.fast
@pytest.mark.parametrize("layer", ["mamba2", "mamba1"])
def test_compaction_parity(layer):
    """Narrowing == one rung == solo ``generate()``, token for token,
    across a mixed workload whose occupancy spans several rungs."""
    cfg = tiny_cfg(layer)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    off, on, eng = run_pair(params, cfg, lambda: mixed_requests(4))
    assert on == off
    for r, got in zip(mixed_requests(4), on):
        want = generate(params, cfg, np.asarray(r.prompt_ids)[None],
                        r.resolve_key(), max_new_tokens=r.max_new_tokens)
        assert got == np.asarray(want)[0, len(r.prompt_ids):].tolist()
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
    eng = ladder_engine(params, cfg, capacity=4)
    cold = streams(eng.run(reqs()))  # populates the cache
    warm = streams(eng.run(reqs()))  # full hits, compacted ticks
    assert cold == baseline
    assert warm == baseline
    assert eng.metrics.prefix_full_hits > 0


@pytest.mark.fast
def test_compaction_preempt_resume_parity():
    """A priority preemption mid-stream on a narrowing engine: swap-out
    and restore operate on the full pool between ticks, so the resumed
    stream continues bit-exactly — compared against the one-rung engine
    running the identical priority workload."""
    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)

    def drive(floor):
        eng = ladder_engine(params, cfg, floor=floor, capacity=2,
                            tokens_per_tick=2)
        los = [GenerationRequest(
            prompt_ids=np.arange(1 + i, 9 + i, dtype=np.int32),
            max_new_tokens=16 + 8 * i, seed=1 + i) for i in range(2)]
        hi = GenerationRequest(prompt_ids=np.arange(2, 10, dtype=np.int32),
                               max_new_tokens=6, seed=9, priority=5)
        i_los = [eng.submit(r) for r in los]
        for _ in range(2):
            eng.step()
        i_hi = eng.submit(hi)
        while eng.pending:
            eng.step()
        return ([eng.results[i].new_tokens.tolist() for i in i_los],
                eng.results[i_hi].new_tokens.tolist(), eng)

    off_lo, off_hi, off_eng = drive(8)
    on_lo, on_hi, on_eng = drive(1)
    assert (off_eng._rungs, on_eng._rungs) == ((2,), (1, 2))
    assert off_eng.metrics.preemptions >= 1
    assert on_eng.metrics.preemptions >= 1
    assert on_eng.metrics.summary()["compaction"]["ticks_compacted"] > 0
    assert on_lo == off_lo
    assert on_hi == off_hi


@pytest.mark.slow
def test_compaction_migration_parity():
    """Disaggregated prefill->decode migration with narrowing engines at
    BOTH tiers: the artifact restore lands in the full pool and the
    narrow decode ticks continue it bit-exactly."""
    cfg = tiny_cfg(prefill_chunk_tokens=CHUNK,
                   prefill_tokens_per_tick=CHUNK,
                   disagg_prompt_threshold=24)

    params = init_lm_params(jax.random.PRNGKey(0), cfg)

    def run(floor):
        with rung_floor(floor):
            router = RequestRouter(
                params, cfg, num_replicas=2, capacity=4,
                roles=["prefill", "decode"],
            )
        return router.run(mixed_requests(3, long_len=48))

    assert streams(run(1)) == streams(run(8))


@pytest.mark.slow
def test_compaction_parity_tp_mesh():
    """(data=2, model=2) serving mesh: the lanes keep the data-axis
    tiling (shard-local gathers, a rung a multiple of the shard count)
    and streams stay bit-identical to the one-rung 2-D engine."""
    cfg = tiny_cfg(prefill_chunk_tokens=CHUNK,
                   prefill_tokens_per_tick=CHUNK,
                   serving_data_shards=2, serving_model_shards=2)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    off, on, eng = run_pair(
        params, cfg, lambda: mixed_requests(4, long_len=40)
    )
    assert on == off
    assert dict(eng.mesh.shape) == {"data": 2, "model": 2}
    # every width tiles over both data shards
    assert eng._rungs == (2, 4, 8)
    comp = eng.metrics.summary()["compaction"]
    assert comp["ticks_compacted"] > 0
    assert all(int(w) % 2 == 0 for w in comp["bucket_histogram"])


# ------------------------------------------------------ ladder + hysteresis


@pytest.mark.fast
@pytest.mark.parametrize("capacity,shards,rungs", [
    (96, 1, (8, 16, 32, 64, 96)),   # the chat cell
    (16, 1, (8, 16)),               # the long-document cell
    (12, 1, (8, 12)),               # the capacity need not be a power of 2
    (8, 1, (8,)),                   # at most the floor: one rung,
    (4, 1, (4,)),                   # exactly the full-width program
    (96, 2, (16, 32, 64, 96)),      # 48 slots a shard: 8, 16, 32, 48 lanes
    (16, 2, (16,)),                 # 8 slots a shard: one rung
])
def test_ladder_by_capacity_and_shards(capacity, shards, rungs):
    """The ladder depends on the capacity and the shard count alone: the
    floor a shard, doubled while under the shard's slots, then the
    capacity; every rung tiles over the shards."""
    assert tick_rungs(capacity, shards) == rungs
    assert all(w % shards == 0 for w in rungs)
    if shards == 1:
        cfg = tiny_cfg()
        params = init_lm_params(jax.random.PRNGKey(0), cfg)
        assert ServingEngine(params, cfg, capacity=capacity)._rungs == rungs


@pytest.mark.fast
def test_rung_grows_immediately_shrinks_with_hysteresis(monkeypatch):
    """The rung must cover the live slots the moment they exist (growth
    can't lag a tick — the gather would drop a stream) but holds through
    ``RUNG_HYSTERESIS_TICKS`` of lower occupancy before narrowing, so
    jitter around a rung's edge doesn't flip programs every tick."""
    monkeypatch.setattr(engine_mod, "RUNG_HYSTERESIS_TICKS", 3)
    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = ladder_engine(params, cfg, capacity=8)
    width = lambda: eng._rungs[eng._rung]
    # one long-budget request -> rung 1
    eng.submit(GenerationRequest(prompt_ids=np.arange(1, 9, dtype=np.int32),
                                 max_new_tokens=40, seed=1))
    eng.step()
    assert width() == 1
    # two more live slots -> need 4: growth is immediate
    for i in range(2):
        eng.submit(GenerationRequest(
            prompt_ids=np.arange(2, 10, dtype=np.int32),
            max_new_tokens=2, seed=2 + i))
    eng.step()
    assert width() == 4
    # the short requests finish; the rung holds for hysteresis ticks
    widths = []
    while eng.pending:
        eng.step()
        widths.append(width())
    assert widths[:2] == [4, 4], widths  # held (streak 1, 2)
    assert 1 in widths  # ...then narrowed back down
    # and the stream still matches the one-rung engine
    off = ServingEngine(params, cfg, capacity=8)
    got = off.run([GenerationRequest(
        prompt_ids=np.arange(1, 9, dtype=np.int32), max_new_tokens=40, seed=1)])
    assert eng.results[0].new_tokens.tolist() == \
        got[0].new_tokens.tolist()


def _visit_every_rung(eng, long_len=None):
    """Drive ``eng`` through every rung of its ladder: a burst that fills
    the pool, drained while single requests keep it from emptying."""
    n = eng.capacity
    for r in mixed_requests(n, seed=3, max_new=(4, 24), long_len=long_len):
        eng.submit(r)
    while eng.pending:
        eng.step()
    hist = eng.metrics.summary()["compaction"]["bucket_histogram"]
    assert {int(w) for w in hist} == set(eng._rungs), hist


@pytest.mark.fast
@pytest.mark.parametrize("stack", ["ssm", "hybrid"])
def test_first_tick_brings_the_whole_ladder(stack, monkeypatch):
    """A launch shape the engine has not run brings its whole ladder with
    it: after the FIRST tick (hybrid: the first at each page-count bucket)
    every rung is traced, one trace a rung, and a run that then visits
    every rung traces nothing — in ``engine`` or in ``state_cache``."""
    monkeypatch.setattr(engine_mod, "RUNG_HYSTERESIS_TICKS", 0)
    cfg = hybrid_cfg() if stack == "hybrid" else tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    # a capacity no other test of this process uses: its traces are new
    eng = ladder_engine(params, cfg, capacity=7, tokens_per_tick=3)
    assert eng._rungs == (1, 2, 4, 7)
    t0, g0 = ENGINE_TRACES["tick"], dict(state_cache.TRACE_COUNTS)
    eng.submit(GenerationRequest(prompt_ids=np.arange(1, 9, dtype=np.int32),
                                 max_new_tokens=3, seed=1))
    while eng.pending:
        eng.step()
    assert eng.metrics.ticks == 1
    assert ENGINE_TRACES["tick"] == t0 + len(eng._rungs)
    if stack == "hybrid":
        # a longer request: a second page bucket, a second ladder
        eng.submit(GenerationRequest(
            prompt_ids=np.arange(40, dtype=np.int32) % 64,
            max_new_tokens=3, seed=2))
        while eng.pending:
            eng.step()
        assert len(eng._warm_shapes) == 2
        assert ENGINE_TRACES["tick"] == t0 + 2 * len(eng._rungs)
    # a run that visits every rung: the pure-SSM engine traces nothing; the
    # hybrid may meet one more page bucket, which again brings every rung
    long_len = 40 if stack == "hybrid" else None
    _visit_every_rung(eng, long_len)
    shapes = len(eng._warm_shapes)
    assert shapes == (1 if stack == "ssm" else 3)
    assert ENGINE_TRACES["tick"] == t0 + shapes * len(eng._rungs)
    _visit_every_rung(eng, long_len)  # and again: flat
    assert len(eng._warm_shapes) == shapes
    assert ENGINE_TRACES["tick"] == t0 + shapes * len(eng._rungs)
    # the gather and the write-back are inside the tick's program
    assert state_cache.TRACE_COUNTS == g0


@pytest.mark.fast
@pytest.mark.parametrize("stack", ["ssm", "hybrid"])
def test_warm_launches_change_nothing(stack, monkeypatch):
    """The ladder's warm-up runs every other rung with nothing live, the
    whole pool among them: the pool a first tick leaves is, bit for bit,
    the pool of an engine that never ran them — the row of a slot parked
    mid-prefill and the rows of the empty slots included."""
    cfg = (hybrid_cfg() if stack == "hybrid"
           else tiny_cfg(prefill_chunk_tokens=CHUNK,
                         prefill_tokens_per_tick=CHUNK))
    params = init_lm_params(jax.random.PRNGKey(0), cfg)

    def first_tick():
        eng = ladder_engine(params, cfg, capacity=4, tokens_per_tick=2)
        assert eng._rungs == (1, 2, 4)
        eng.submit(GenerationRequest(
            prompt_ids=np.arange(1, 9, dtype=np.int32),
            max_new_tokens=12, seed=1))
        eng.submit(GenerationRequest(  # three chunks: parked at the tick
            prompt_ids=np.arange(3 * CHUNK, dtype=np.int32) % 64,
            max_new_tokens=4, seed=2))
        while not eng.metrics.ticks:
            eng.step()
        assert any(t.status.name != "DECODE" for t in eng._slots.values())
        return eng

    warmed = first_tick()
    monkeypatch.setattr(ServingEngine, "_warm_ladder",
                        lambda self, width, bucket: None)
    bare = first_tick()
    monkeypatch.undo()
    assert warmed._warm_shapes and not bare._warm_shapes
    # the trash page (row 0 of the page axis) holds garbage by contract
    drop_trash = lambda path, x: (
        x[:, 1:] if "attn_blocks" in jax.tree_util.keystr(path) else x)
    a, b = (jax.tree_util.tree_map_with_path(drop_trash, e.pool)
            for e in (warmed, bare))
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a),
                            jax.tree.leaves(b)):
        assert np.array_equal(np.asarray(x), np.asarray(y)), \
            jax.tree_util.keystr(path)
    # and both finish the same streams
    for eng in (warmed, bare):
        while eng.pending:
            eng.step()
    assert streams([warmed.results[i] for i in (0, 1)]) == \
        streams([bare.results[i] for i in (0, 1)])


# ------------------------------------------------------------------ in place


@pytest.mark.fast
@pytest.mark.parametrize("stack", ["ssm", "hybrid"])
def test_write_back_is_a_row_write_in_place(stack):
    """Structure of a NARROW tick, by jaxpr (after
    ``test_prefill.test_tick_updates_the_pool_in_place``, which reads the
    full width): nothing but an in-place write (``dynamic_update_slice`` /
    ``scatter``) produces an array of a pool leaf's shape — no ``select_n``
    over the pool, no gather at the pool's width, no copy — and the lanes
    that are gathered have the rung's width."""
    from tests.test_prefill import _walk_eqns

    S, W, steps = 6, 2, 4
    cfg = hybrid_cfg() if stack == "hybrid" else tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    from mamba_distributed_tpu.serving.prefill import cast_decode_params

    dparams = cast_decode_params(params, cfg=cfg)
    pool = state_cache.init_pool(cfg, capacity=S)
    lanes = (jax.numpy.zeros((W,), "int32"), jax.numpy.ones((W,), bool))
    kv = (() if stack == "ssm" else
          (jax.numpy.zeros((W, 4), "int32"), jax.numpy.zeros((W,), "int32")))
    jaxpr = jax.make_jaxpr(
        lambda p, q, ln: engine_mod._tick(p, q, *kv, lanes=ln, cfg=cfg,
                                          k_max=5, steps=steps)
    )(dparams, pool, lanes)
    rows = [pool["state"]["blocks"], pool["logits"], pool["meta"]]
    pool_shapes = {x.shape for x in jax.tree.leaves(rows) if x.ndim > 1}
    lane_shapes = {
        x.shape[:ax] + (W,) + x.shape[ax + 1:]
        for ax, tree in ((1, rows[0]), (0, rows[1:]))
        for x in jax.tree.leaves(tree) if x.ndim > 1
    }
    shape = lambda v: getattr(v.aval, "shape", None)
    gathered = set()
    for eqn, wraps in _walk_eqns(jaxpr.jaxpr):
        name = eqn.primitive.name
        if wraps or name == "pallas_call":
            continue
        for out in eqn.outvars:
            if shape(out) in pool_shapes:
                assert name in ("scatter", "dynamic_update_slice"), eqn
            if name == "gather":
                gathered.add(shape(out))
    assert lane_shapes <= gathered
    # the write-back alone: a loop over the kept lanes of row writes, and
    # no ``select_n`` of any shape (a pad lane is skipped, not tested)
    whole = {"blocks": rows[0], "logits": rows[1], "meta": rows[2]}
    back = jax.make_jaxpr(state_cache.scatter_rows)(
        whole, state_cache.gather_rows(whole, *lanes), *lanes)
    eqns = [e for e, _ in _walk_eqns(back.jaxpr)]
    names = [e.primitive.name for e in eqns]
    assert "while" in names
    # (an index is wrapped by a scalar select; no array is selected)
    assert all(shape(e.outvars[0]) == () for e in eqns
               if e.primitive.name == "select_n")
    assert names.count("dynamic_update_slice") == len(jax.tree.leaves(rows))


# ------------------------------------------------------------------ honesty


class _Spans:
    """The least a tracer is: it keeps (name, attrs) of every span."""

    enabled = True

    def __init__(self):
        self.spans = []

    @contextlib.contextmanager
    def span(self, name, **attrs):
        self.spans.append((name, attrs))
        yield

    def event(self, name, **attrs):
        pass


@pytest.mark.fast
def test_serving_tick_span_carries_width(monkeypatch):
    """``serving_tick`` carries ``width``, the lanes launched: a rung of
    the ladder, never under ``live``, and what the tick's record bills."""
    monkeypatch.setattr(engine_mod, "RUNG_HYSTERESIS_TICKS", 0)
    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    spans = _Spans()
    eng = ladder_engine(params, cfg, capacity=8, tracer=spans,
                        tokens_per_tick=4)
    eng.run(mixed_requests(5, seed=3, max_new=(4, 40)))
    ticks = [a for n, a in spans.spans if n == "serving_tick"]
    assert len(ticks) == eng.metrics.ticks > 0
    for a in ticks:
        assert a["width"] in eng._rungs
        assert a["width"] >= a["live"] >= 1
        assert a["occupied"] >= a["live"]
    assert min(a["width"] for a in ticks) < 8
    hist = eng.metrics.summary()["compaction"]["bucket_histogram"]
    assert hist == {str(w): sum(a["width"] == w for a in ticks)
                    for w in sorted({a["width"] for a in ticks})}


@pytest.mark.fast
def test_goodput_bills_launched_lanes(tmp_path):
    """Tick records price slot_lanes at the launched width: at one live
    slot in an 8-slot pool the wasted token lanes collapse from
    ~capacity*steps to ~rung*steps, and the width stamps ride the records
    (histogram + lanes_saved in summary()) — of a one-rung engine too."""
    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    from mamba_distributed_tpu.utils.metrics import ServingMetrics

    def ticks_of(floor, name):
        jsonl = str(tmp_path / name)
        metrics = ServingMetrics(8, jsonl_path=jsonl)
        eng = ladder_engine(params, cfg, floor=floor, capacity=8,
                            metrics=metrics, tokens_per_tick=4)
        eng.run([GenerationRequest(
            prompt_ids=np.arange(1, 9, dtype=np.int32),
            max_new_tokens=12, seed=1)])
        ticks = [json.loads(ln) for ln in open(jsonl)
                 if json.loads(ln).get("kind") == "serving_tick"]
        assert ticks
        return ticks, metrics.summary()["compaction"]

    ticks, comp = ticks_of(1, "ladder.jsonl")
    for t in ticks:
        assert t["compaction_width"] == 1  # one live slot -> one lane
    # lanes billed at the rung: in a prefill-free window the bill is
    # 1 lane * 4 sub-steps exactly (a full-width tick bills 32)
    steady = [t for t in ticks if not t.get("prefill_oneshot_tokens")
              and not t.get("prefill_chunk_tokens")]
    assert steady
    for t in steady:
        assert t["useful_tokens"] + t["wasted_token_lanes"] == 4
    assert comp["bucket_histogram"] == {"1": len(ticks)}
    assert comp["lanes_saved"] == len(ticks) * (8 - 1) * 4
    assert comp["recompiles"] == 1
    # the stamp is on every engine's ticks: one rung bills the capacity
    ticks, comp = ticks_of(8, "one.jsonl")
    assert all(t["compaction_width"] == 8 for t in ticks)
    assert comp == {"ticks_compacted": 0, "recompiles": 0,
                    "bucket_histogram": {"8": len(ticks)}, "lanes_saved": 0}


@pytest.mark.fast
def test_spec_lanes_billed_at_rung(tmp_path):
    """Speculative ticks price capacity*(K+1) lanes at full width; a
    narrow launch's records bill rung*(K+1) — rejected drafts still land
    in wasted_token_lanes, idle slots no longer do."""
    cfg = tiny_cfg(spec_tokens=3)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    from mamba_distributed_tpu.utils.metrics import ServingMetrics

    jsonl = str(tmp_path / "spec.jsonl")
    metrics = ServingMetrics(8, jsonl_path=jsonl)
    eng = ladder_engine(params, cfg, capacity=8, metrics=metrics)
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
    """The jsonl stream's width stamps surface as the report's
    "compaction:" line."""
    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    from mamba_distributed_tpu.utils.metrics import ServingMetrics

    jsonl = str(tmp_path / "rep.jsonl")
    metrics = ServingMetrics(8, jsonl_path=jsonl)
    ladder_engine(params, cfg, capacity=8,
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
