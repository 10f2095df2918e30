"""3-D serving mesh: the pipeline ``stage`` axis (ISSUE 19).

What's covered (docs/SERVING.md "3-D serving mesh"):

  * MESH — ``serving_mesh(stage_shards=)`` grows the middle ``stage``
    axis only when > 1; ``stage_shards=1`` returns the 2-D mesh
    UNCHANGED (the ``mesh.shape`` pins of the 2-D fabric hold byte for
    byte).
  * SCHEDULE — ``parallel/pipeline.pipelined_decode_layers`` (the
    stateful GPipe decode clock: lane microbatches flowing through
    stage-resident layer groups) is BITWISE identical to the
    sequential layer scan at every microbatch count.
  * PARITY — engine streams at ``serving_stage_shards > 1`` bit-match
    solo ``generate()`` across mamba1/mamba2/hybrid, chunked longs,
    spec K>0, prefix-warm, park/resume, disagg migration, and the
    (2,2,1)/(1,2,2) mesh points (the GSPMD track: same program,
    different placement).
  * HONESTY — ``stage=1`` keeps records/summaries byte-stable (no
    pipeline stamps anywhere); at ``stage > 1`` the explicit clock's
    warmup/drain bubble is billed into goodput's wasted lanes.
  * STABILITY — repeated pipelined ticks reuse one trace per pow2
    lane bucket (TRACE_COUNTS flat; no per-tick recompiles).

The heavy matrix points are marked ``slow``: the "not slow" subset
here is the lean smoke spine.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mamba_distributed_tpu.config import ModelConfig
from mamba_distributed_tpu.inference.generate import generate
from mamba_distributed_tpu.models.lm import (
    init_lm_params,
    init_lm_state,
    lm_step,
)
from mamba_distributed_tpu.parallel.mesh import serving_mesh
from mamba_distributed_tpu.parallel.sharding import (
    validate_serving_stage_shards,
)
from mamba_distributed_tpu.serving.engine import (
    ServingEngine,
    TRACE_COUNTS,
)
from mamba_distributed_tpu.serving.scheduler import GenerationRequest
from mamba_distributed_tpu.utils.metrics import ServingMetrics

pytestmark = pytest.mark.pipe_serve

CHUNK = 32


def tiny_cfg(layer="mamba2", **kw):
    kw.setdefault("prefill_chunk_tokens", CHUNK)
    kw.setdefault("prefill_tokens_per_tick", CHUNK)
    kw.setdefault("serving_stage_shards", 2)
    kw.setdefault("n_layer", 2)
    return ModelConfig(d_model=32, vocab_size=64, ssm_layer=layer,
                       headdim=8, chunk_size=16, d_state=16,
                       compute_dtype="float32", **kw)


def hybrid_cfg(**kw):
    """CPU-runnable hybrid whose BOTH layer families tile over 2
    stages: 4 layers, attention at (1, 3) -> 2 mamba + 2 attn."""
    return tiny_cfg(n_layer=4, attn_layer_idx=(1, 3), attn_num_heads=4,
                    attn_num_kv_heads=2, remat=False, kv_page_tokens=8,
                    kv_slot_tokens=128, **kw)


def rand_prompt(n, seed=1, vocab=64):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, vocab), np.int32
    )


def solo(params, cfg, prompt, key, mesh=None, **kw):
    out = generate(params, cfg, jnp.asarray(prompt, jnp.int32)[None], key,
                   mesh=mesh, **kw)
    return np.asarray(out)[0, len(prompt):].tolist()


def mixed_requests(n_short=3, n_long=1, max_new=6, **kw):
    """Short prompts plus chunk-spanning longs (> 2 * CHUNK tokens)."""
    reqs = []
    for i in range(n_short):
        reqs.append(GenerationRequest(
            prompt_ids=rand_prompt(5 + 3 * i, seed=10 + i),
            max_new_tokens=max_new, key=jax.random.PRNGKey(100 + i), **kw))
    for i in range(n_long):
        reqs.append(GenerationRequest(
            prompt_ids=rand_prompt(2 * CHUNK + 7 + i, seed=50 + i),
            max_new_tokens=max_new, key=jax.random.PRNGKey(200 + i), **kw))
    return reqs


def assert_parity(params, cfg, requests, results, mesh=None):
    for r, res in zip(requests, results):
        want = solo(params, cfg, r.prompt_ids, r.key, mesh=mesh,
                    max_new_tokens=r.max_new_tokens,
                    top_k=r.top_k if r.top_k != 50 else 50)
        assert res.new_tokens.tolist() == want


# ----------------------------------------------------------------- mesh


def test_serving_mesh_3d_shape():
    """stage_shards > 1 grows the middle axis; stage_shards = 1 keeps
    the 2-D mesh (no size-1 stage axis is ever materialized, so the
    2-D fabric's ``mesh.shape`` pins hold)."""
    m = serving_mesh(1, model_shards=1, stage_shards=2)
    assert dict(m.shape) == {"data": 1, "stage": 2, "model": 1}
    assert m.axis_names == ("data", "stage", "model")
    m = serving_mesh(2, model_shards=2, stage_shards=2)
    assert dict(m.shape) == {"data": 2, "stage": 2, "model": 2}
    # the byte-stability contract: stage=1 is the exact 2-D mesh
    m = serving_mesh(2, model_shards=2)
    assert dict(m.shape) == {"data": 2, "model": 2}
    assert m.axis_names == ("data", "model")
    with pytest.raises(ValueError, match="devices"):
        serving_mesh(2, model_shards=2, stage_shards=4)
    with pytest.raises(ValueError, match="stage_shards"):
        serving_mesh(1, stage_shards=0)


def test_stage_shard_validation_errors():
    """Indivisible layer stacks are rejected at CONSTRUCTION with a
    named error (the validate_serving_model_shards precedent), not as
    a GSPMD error mid-flight."""
    # pure-SSM: n_layer must tile over the stages
    with pytest.raises(ValueError, match="layer stack"):
        validate_serving_stage_shards(tiny_cfg(n_layer=3), 2)
    # hybrid: BOTH stacked families shard separately, so both must
    # tile — 4 layers with attention at (1,) is 3 mamba + 1 attn
    bad = tiny_cfg(n_layer=4, attn_layer_idx=(1,), attn_num_heads=4,
                   attn_num_kv_heads=2, remat=False, kv_page_tokens=8,
                   kv_slot_tokens=64)
    with pytest.raises(ValueError, match="blocks"):
        validate_serving_stage_shards(bad, 2)
    # divisible configs validate clean
    validate_serving_stage_shards(tiny_cfg(), 2)
    validate_serving_stage_shards(hybrid_cfg(), 2)
    # the config knob itself rejects nonsense
    with pytest.raises(ValueError, match="serving_stage_shards"):
        tiny_cfg(serving_stage_shards=-1)
    # engine construction routes through the validator
    cfg = tiny_cfg(n_layer=3)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="layer stack"):
        ServingEngine(params, cfg, capacity=2)


# ------------------------------------------------------------- schedule


@pytest.mark.slow
def test_pipelined_decode_layers_unit_parity():
    """The explicit GPipe decode clock is BITWISE the sequential layer
    scan at every legal microbatch count (including the degenerate
    n_micro=1 flush): logits AND the advanced conv/SSM carries.
    Marked slow (three pipelined compiles); the non-slow engine test
    below pins the same schedule bitwise end-to-end."""
    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    lanes = 4
    state = init_lm_state(cfg, lanes)
    tok = jnp.asarray([3, 9, 27, 41], jnp.int32)
    ref_logits, ref_state = lm_step(params, cfg, state, tok)
    mesh = serving_mesh(1, model_shards=1, stage_shards=2)
    for n_micro in (1, 2, 4):
        logits, new_state = lm_step(params, cfg, state, tok,
                                    pipeline=(mesh, n_micro))
        assert np.array_equal(np.asarray(logits), np.asarray(ref_logits)), \
            f"logits diverged at n_micro={n_micro}"
        for a, b in zip(jax.tree.leaves(new_state),
                        jax.tree.leaves(ref_state)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), \
                f"state diverged at n_micro={n_micro}"
    # indivisible shapes are loud
    with pytest.raises(ValueError, match="n_micro"):
        lm_step(params, cfg, state, tok, pipeline=(mesh, 3))


def test_pipelined_decode_holds_masked_rows():
    """``lm_step(state_mask=)`` under the GPipe clock: the mask travels
    the stages with its lanes, so held rows keep their carries bit for
    bit and the rest get what the same clock computes unmasked."""
    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    state = init_lm_state(cfg, 4)
    tok = jnp.asarray([3, 9, 27, 41], jnp.int32)
    _, state = lm_step(params, cfg, state, tok)  # a non-zero carry
    mask = np.array([True, False, False, True])
    pipeline = (serving_mesh(1, model_shards=1, stage_shards=2), 2)
    ref_logits, ref_state = lm_step(params, cfg, state, tok,
                                    pipeline=pipeline)
    logits, new_state = lm_step(params, cfg, state, tok, pipeline=pipeline,
                                state_mask=jnp.asarray(mask))
    assert np.array_equal(np.asarray(logits)[mask],
                          np.asarray(ref_logits)[mask])
    for old, new, want in zip(*map(jax.tree.leaves,
                                   (state, new_state, ref_state))):
        old, new, want = map(np.asarray, (old, new, want))
        assert np.array_equal(new[:, ~mask], old[:, ~mask])
        assert np.array_equal(new[:, mask], want[:, mask])
        assert not np.array_equal(want[:, ~mask], old[:, ~mask])


# --------------------------------------------------------------- parity


@pytest.mark.slow
def test_engine_parity_and_flat_traces_stage2(monkeypatch):
    """(data=1, stage=2, model=1) with a ladder of rungs 1, 2, 4: every
    stream bit-matches solo generate(), the explicit microbatched
    clock engages (pipelined ticks billed bubbles), and the first tick
    traces ONE program a rung — TRACE_COUNTS stay flat across every
    tick after it.  Marked slow with the rest of the compile-heavy
    matrix; `pytest -m pipe_serve` runs the whole tier standalone."""
    from mamba_distributed_tpu.serving import engine as engine_mod

    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    monkeypatch.setattr(engine_mod, "RUNG_FLOOR_LANES", 1)
    monkeypatch.setattr(engine_mod, "RUNG_HYSTERESIS_TICKS", 0)
    eng = ServingEngine(params, cfg, capacity=4, tokens_per_tick=2)
    assert eng._rungs == (1, 2, 4)
    assert dict(eng.mesh.shape) == {"data": 1, "stage": 2, "model": 1}
    assert eng.stage_shards == 2
    # staggered budgets so occupancy decays through >1 pow2 bucket;
    # chunked longs ride the slow matrix below
    reqs = [GenerationRequest(prompt_ids=rand_prompt(5 + 3 * i, seed=10 + i),
                              max_new_tokens=m, key=jax.random.PRNGKey(100 + i))
            for i, m in enumerate((4, 8, 8))]
    for r in reqs:
        eng.submit(r)
    ticks_at = []
    while eng.pending:
        before = TRACE_COUNTS["tick"]
        eng.step()
        ticks_at.append((before, TRACE_COUNTS["tick"]))
    # one compiled tick trace a rung, all of them at the first tick —
    # never one per tick (that would be a per-tick recompile)
    first = next(i for i, (b, a) in enumerate(ticks_at) if a > b)
    assert ticks_at[first][1] - ticks_at[first][0] == len(eng._rungs)
    assert all(a == b for b, a in ticks_at[first + 1:])
    widths = {w for w in eng.metrics.compaction_hist}
    assert len(widths) > 1  # the run visited more than one rung
    assert len(ticks_at) - first > len(widths)  # and repeated ticks
    results = [eng.results[i] for i in range(len(reqs))]
    assert_parity(params, cfg, reqs, results)
    # the explicit clock engaged and billed its ramp
    pipe = eng.metrics.summary()["pipeline"]
    assert pipe["stage_shards"] == 2
    assert pipe["pipelined_ticks"] > 0
    assert pipe["bubble_lanes"] > 0
    assert eng.metrics.summary()["goodput"]["wasted_token_lanes"] >= \
        pipe["bubble_lanes"]


@pytest.mark.slow
@pytest.mark.parametrize("shape", [(2, 2, 1), (1, 2, 2)])
def test_engine_parity_matrix_3d(shape):
    """The full 3-D points on the virtual 8-device mesh: stage
    composes with sharded slot pools (data=2) and TP weights
    (model=2); streams bit-match generate(mesh=) (the GSPMD track —
    same program, different placement)."""
    data, stage, model = shape
    cfg = tiny_cfg(serving_data_shards=data, serving_model_shards=model)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, capacity=4, tokens_per_tick=2)
    assert dict(eng.mesh.shape) == {"data": data, "stage": stage,
                                    "model": model}
    reqs = mixed_requests()
    results = eng.run(reqs)
    assert_parity(params, cfg, reqs, results, mesh=eng.mesh)


@pytest.mark.slow
@pytest.mark.parametrize("layer", ["mamba1", "hybrid"])
def test_engine_parity_layers_stage2(layer):
    """mamba1 and the hybrid stack at (1, 2, 1), chunked longs
    included: per-layer KV page pools ride their attn_blocks family's
    stage shard; hybrids run the GSPMD track (the explicit clock is
    pure-SSM only)."""
    cfg = hybrid_cfg() if layer == "hybrid" else tiny_cfg(layer)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, capacity=4, tokens_per_tick=2)
    assert eng.stage_shards == 2
    reqs = mixed_requests()
    results = eng.run(reqs)
    assert_parity(params, cfg, reqs, results, mesh=eng.mesh)
    if layer == "hybrid":
        assert eng.page_pool.pages_in_use == 0  # full page recycle


@pytest.mark.slow
@pytest.mark.spec
def test_spec_stage2_parity():
    """Speculative decoding at stage=2 rides the GSPMD track (verify
    launches are chunk-shaped): greedy spec streams stay bit-identical
    to solo greedy generate()."""
    cfg = tiny_cfg(spec_tokens=3)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2)
    reqs = mixed_requests(n_short=2, n_long=1, max_new=8, top_k=1)
    results = eng.run(reqs)
    for r, res in zip(reqs, results):
        want = solo(params, cfg, r.prompt_ids, r.key, top_k=1,
                    max_new_tokens=r.max_new_tokens)
        assert res.new_tokens.tolist() == want


@pytest.mark.slow
def test_prefix_warm_stage2_parity():
    """Prefix-cache warm streams at stage=2 match their own cold run
    (a snapshot is the identical chunk computation's literal output,
    whatever the layer placement)."""
    cfg = tiny_cfg(prefix_cache_entries=8)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    prompt = rand_prompt(2 * CHUNK + 5, seed=7)
    key = jax.random.PRNGKey(11)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2)
    cold = eng.run([GenerationRequest(prompt_ids=prompt, max_new_tokens=6,
                                      key=key)])[0]
    warm = eng.run([GenerationRequest(prompt_ids=prompt, max_new_tokens=6,
                                      key=key)])[0]
    assert eng.metrics.prefix_full_hits + eng.metrics.prefix_partial_hits > 0
    assert warm.new_tokens.tolist() == cold.new_tokens.tolist()
    assert cold.new_tokens.tolist() == solo(params, cfg, prompt, key,
                                            max_new_tokens=6)


@pytest.mark.slow
@pytest.mark.sessions
def test_park_resume_stage2_parity():
    """Park a mid-decode stream off a stage=2 engine and resume it on
    a FRESH stage=2 engine: the token stream continues bit-exactly
    (per-stage carries serialize/restore like any slot state)."""
    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    prompt = rand_prompt(9, seed=3)
    key = jax.random.PRNGKey(5)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2)
    rid = eng.submit(GenerationRequest(prompt_ids=prompt, max_new_tokens=10,
                                       key=key))
    request, snap = None, None
    for _ in range(100):
        try:
            request, snap = eng.park(rid)
            break
        except ValueError:
            eng.step()
    assert snap is not None, "request never became parkable"
    head = list(snap.get("new_tokens", []))
    assert head, "park artifact carries the already-streamed tokens"
    eng2 = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2)
    rid2 = eng2.submit_migrated(request, snap)
    while eng2.pending:
        eng2.step()
    # the resumed record carries head + continuation (submit_migrated
    # restores the streamed prefix so budgets/indices line up)
    full = eng2.results[rid2].new_tokens.tolist()
    assert full[: len(head)] == head
    assert full == solo(params, cfg, prompt, key, max_new_tokens=10)


@pytest.mark.slow
@pytest.mark.disagg
def test_disagg_migration_stage2_parity():
    """Disaggregated prefill->decode handoff between stage=2 replicas:
    longs prefill on one tier, migrate, decode on the other — streams
    bit-match solo generate()."""
    from mamba_distributed_tpu.serving.router import RequestRouter

    cfg = tiny_cfg(disagg_prompt_threshold=CHUNK)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    reqs = mixed_requests(n_short=2, n_long=1, max_new=5)
    router = RequestRouter(params, cfg, num_replicas=2, capacity=2,
                           roles=["prefill", "decode"], tokens_per_tick=2)
    results = router.run(reqs)
    assert_parity(params, cfg, reqs, results)
    assert router.migrations == 1


# ------------------------------------------------- stage=1 byte-stability


def test_stage1_is_byte_stable(tmp_path):
    """serving_stage_shards=1 (the default) is the exact 2-D fabric:
    no mesh below any sharding knob, no pipeline stamps on tick
    records, summary()["pipeline"] stays None."""
    cfg = tiny_cfg(serving_stage_shards=1)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    jsonl = str(tmp_path / "ticks.jsonl")
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2,
                        metrics=ServingMetrics(2, jsonl_path=jsonl))
    assert eng.mesh is None
    assert eng.stage_shards == 1
    eng.run([GenerationRequest(prompt_ids=rand_prompt(5), max_new_tokens=4,
                               key=jax.random.PRNGKey(1))])
    assert eng.metrics.summary()["pipeline"] is None
    import json

    with open(jsonl) as f:
        ticks = [json.loads(ln) for ln in f
                 if '"serving_tick"' in ln]
    assert ticks
    for t in ticks:
        assert "stage_shards" not in t
        assert "bubble_lanes" not in t


# --------------------------------------------------- bubble accounting


def test_bubble_accounting_injected_widths():
    """Pure-metrics check of the bubble bill at injected lane widths:
    bubble lanes add to goodput's computed (wasted) lanes, the
    summary block aggregates only pipelined ticks, and stage stamps
    appear exactly when passed."""
    m = ServingMetrics(8)
    m.configure_pipeline(2)
    # a pipelined tick at width 8, n_micro 2: ramp idles
    # (stages-1) * (8//2) * steps lanes
    for width, n_micro, steps in ((8, 2, 4), (4, 2, 4), (2, 2, 4)):
        bubble = (2 - 1) * (width // n_micro) * steps
        m.record_tick(occupied=width, queue_depth=0,
                      tokens_emitted=width * steps, dt_s=0.01,
                      slot_lanes=width * steps,
                      stage_shards=2, bubble_lanes=bubble)
    # a GSPMD-fallback tick: stamped but zero bubble
    m.record_tick(occupied=8, queue_depth=0, tokens_emitted=32,
                  dt_s=0.01, slot_lanes=32, stage_shards=2,
                  bubble_lanes=0)
    pipe = m.summary()["pipeline"]
    want_bubble = sum((2 - 1) * (w // 2) * 4 for w in (8, 4, 2))
    assert pipe["stage_shards"] == 2
    assert pipe["pipelined_ticks"] == 3  # the zero-bubble tick not counted
    assert pipe["bubble_lanes"] == want_bubble
    lanes = sum(w * 4 for w in (8, 4, 2))
    assert pipe["bubble_fraction"] == round(
        want_bubble / (want_bubble + lanes), 4)
    # goodput bills the bubbles: every emitted token was useful, so
    # wasted == exactly the bubble lanes
    good = m.summary()["goodput"]
    assert good["wasted_token_lanes"] == want_bubble
