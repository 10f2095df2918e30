"""granite-4.0-h-small's block against its plain reference, logit for logit.

Nine Mamba-2 layers and one NoPE attention layer a period, each followed by a
dropless expert layer (top-k of the router's float32 logits, a softmax over
the chosen alone, the experts HELD here computed for every row routed to them,
a shared expert beside them), with four scalars: on the embedding, on each
half-block's output, on attention's scores, on the logits.  The reference is
``benchmark/reference/granite_moe_hybrid.py`` (float32, whole sequence at
once, no cache, the expert layer a loop over the held experts with a gate that
is zero where the expert was not chosen); the weights are its ``init_params``
from a seed, in the program's layout.

Tiny widths (the preset ``granite-h-tiny``: 4 layers, attention at 1 and 3, 8
experts of which 4 are held, 3 a token), float32, matmul precision "highest"
(tests/conftest.py).  The logits are of size 0.004 (a normed stream on an
embedding drawn at ``initializer_range / embedding_multiplier``, under
``lm_head_multiplier`` 1/16), so every tolerance is absolute and stated
against that:

* ``TOL`` 2e-8: the program and the reference order their float32 sums
  differently (a chunked scan against the quadratic form, a fused add+norm
  against two steps, one product over (expert, width) against a loop over the
  experts); measured differences are 1e-9 to 2e-9, the rounding of a
  0.004-sized logit.  The chunked paths add the inter-chunk state
  recurrence's re-association (tests/test_prefill.py: ~1e-6 relative), still
  inside.  No weights here put a row's k-th and (k+1)-th router logit within
  rounding of each other, which is the one place where the two could choose
  different experts.
* ``DROPPED`` 2e-5, a thousand times ``TOL``: the least that changing any one
  part moves a logit here is 9e-5 (a rotary embedding's), then 4e-4 (the
  attention scale's, the gate order's), the most 0.056 (the head's).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import granite_moe_hybrid as ref
from mamba_distributed_tpu.config import ModelConfig, get_preset
from mamba_distributed_tpu.models import lm as lm_mod
from mamba_distributed_tpu.models.lm import (
    init_lm_state,
    lm_forward,
    lm_prefill_chunk,
    lm_step,
)
from mamba_distributed_tpu.serving import GenerationRequest, ServingEngine
from mamba_distributed_tpu.serving import engine as engine_mod
from mamba_distributed_tpu.serving.prefill import chunk_inputs, plan_chunks
from mamba_distributed_tpu.utils.flops import flops_per_token
from tests.test_falcon_h1 import drive  # the engine run to the end, every held logits row kept

pytestmark = pytest.mark.serving

TOL = 2e-8
DROPPED = 2e-5

MULTIPLIERS = ("residual_multiplier", "attention_multiplier",
               "embedding_multiplier", "lm_head_multiplier")
# what the reference reads of a configuration's ``model`` dict
MODEL_KEYS = (
    "d_model", "n_layer", "vocab_size", "expand", "headdim", "ngroups",
    "d_state", "d_conv", "d_intermediate", "norm_eps", "attn_layer_idx",
    "attn_num_heads", "attn_num_kv_heads", "attn_head_dim", "rope_theta",
    "initializer_range", "dt_min", "dt_max", "dt_init_floor", "a_init_min",
    "a_init_max", "moe_num_experts", "moe_top_k", "moe_first_expert",
    "moe_experts_held", "moe_shared_intermediate",
) + MULTIPLIERS


def tiny_cfg(**kw) -> ModelConfig:
    return dataclasses.replace(
        get_preset("granite-h-tiny").model, compute_dtype="float32",
        remat=False, **kw)


def model_dict(cfg: ModelConfig) -> dict:
    m = {k: getattr(cfg, k) for k in MODEL_KEYS}
    return {k: list(v) if isinstance(v, tuple) else v for k, v in m.items()}


def make_params(m, seed=7):
    return jax.jit(lambda k: ref.init_params(k, m, "float32"))(
        jax.random.PRNGKey(seed))


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_cfg()
    m = model_dict(cfg)
    return cfg, m, make_params(m)


@pytest.fixture(params=[256, 4], ids=["masked_form", "grouped_form"])
def form(request, monkeypatch):
    """Both forms of the expert layer at the same tiny shapes: every held
    expert over every row under a gate mask, and rows sorted by expert and
    multiplied in groups (``models/lm.MOE_DENSE_MAX_ROWS`` chooses by the
    rows of the call; the tests move the line, not a flag of the program)."""
    monkeypatch.setattr(lm_mod, "MOE_DENSE_MAX_ROWS", request.param)
    return request.param


def ref_logits(params, m, ids):
    return np.asarray(ref.logits_fn(params, m, jnp.asarray(ids)))


def ids_of(n, seed=1, vocab=512):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (1, n), 0, vocab), np.int32)


# ------------------------------------------------------------ (a) forward


def test_full_forward_logits_match_reference(setup, form):
    cfg, m, params = setup
    ids = ids_of(48)
    got = np.asarray(lm_forward(params, cfg, jnp.asarray(ids)))
    want = ref_logits(params, m, ids)
    assert 0.002 < np.abs(want).max() < 0.02  # the scale TOL is stated against
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL)


# ------------------------------------------------------------ (b) chunked


def test_chunked_prefill_with_left_pad_then_decode_matches_reference(
        setup, form):
    """150 tokens in three 64-token chunks, the first with 42 pad positions
    on its left, through the slot state and the paged cache; then eight
    decode steps.  The chunk's expert load counts its real tokens alone."""
    cfg, m, params = setup
    ids = ids_of(158, seed=2)
    want = ref_logits(params, m, ids)
    prompt = ids[:, :150]
    plan = plan_chunks(150, cfg.effective_prefill_chunk_tokens, force=True)
    assert (plan.n_chunks, plan.pad) == (3, 42)
    state = init_lm_state(cfg, 1, max_len=cfg.kv_slot_tokens)
    rows = 0
    for i in range(plan.n_chunks):
        cids, mask = chunk_inputs(prompt, plan, i)
        logits, state, load = lm_prefill_chunk(
            params, cfg, cids, state, mask, return_load=True)
        rows += int(np.asarray(load)[:-1].sum())
        assert int(load[-1]) <= cfg.n_layer * cfg.moe_held[1]
    assert int(state["attn_meta"][1][0]) == 150
    # a seed's router sends about half of 150 x 3 x 4 choices to the half held
    assert 0.4 < rows / (150 * cfg.moe_top_k * cfg.n_layer) < 0.6
    np.testing.assert_allclose(np.asarray(logits), want[:, 149], rtol=0,
                               atol=TOL)
    for i in range(150, 158):
        logits, state = lm_step(params, cfg, state, jnp.asarray(ids[:, i]))
        np.testing.assert_allclose(np.asarray(logits), want[:, i], rtol=0,
                                   atol=TOL)


# ------------------------------------------------------------ (c) engine


@pytest.mark.parametrize("floor", [8, 1], ids=["top_rung", "narrow_rung"])
def test_engine_answers_each_request_as_if_alone(setup, floor, monkeypatch):
    """Three greedy requests of unequal length share ticks in a four-slot
    engine.  Every logits row the pool held while they decoded is what the
    reference gives that request ALONE at that position: nothing depends on
    who shares a tick or a chunk.  The old layer's capacity at this load
    (3 lanes x 3 choices over 8 experts, factor 1.25: 2 rows an expert)
    dropped rows whenever three lanes chose one expert."""
    cfg, m, params = setup
    monkeypatch.setattr(engine_mod, "RUNG_FLOOR_LANES", floor)
    eng = ServingEngine(params, cfg, capacity=4, tokens_per_tick=4)
    assert eng._rungs == ((4,) if floor == 8 else (1, 2, 4))
    prompts = [ids_of(150, seed=3)[0], ids_of(23, seed=4)[0],
               ids_of(70, seed=5)[0]]
    reqs = [GenerationRequest(prompt_ids=p, max_new_tokens=n, top_k=1, seed=i)
            for i, (p, n) in enumerate(zip(prompts, (10, 17, 13)))]
    tokens, rows, widths = drive(eng, reqs)
    for p, new, (rid, held) in zip(prompts, tokens, rows.items()):
        want = ref_logits(params, m, np.concatenate([p, new])[None])[0]
        assert len(new) in (10, 17, 13) and held
        for n_so_far, row in held:
            np.testing.assert_allclose(
                row, want[len(p) + n_so_far - 1], rtol=0, atol=TOL)
        at = want[len(p) - 1 + np.arange(len(new))]
        assert (at.max(axis=1) - at[np.arange(len(new)), new]).max() <= TOL
    # the launches' counters: about half the rows offered land on the half
    # of the experts held, and the spans' figures add up to the engine's
    ex = eng.metrics.summary()["experts"]
    assert ex["rows_offered"] == (150 + 23 + 70 + 10 + 17 + 13) * (
        cfg.moe_top_k * cfg.n_layer)
    assert 0.4 < ex["rows_share"] < 0.6


def test_load_rides_the_spans(setup, monkeypatch):
    """``serving_tick`` and ``serving_prefill_chunk`` carry the launch's
    expert counters, set once the tick's fetch has returned."""
    from benchmark.harness import SpanRecorder

    cfg, m, params = setup
    spans = SpanRecorder()
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=4,
                        tracer=spans)
    eng.run([GenerationRequest(prompt_ids=ids_of(70, seed=5)[0],
                               max_new_tokens=5, top_k=1, seed=0)])
    per_layer = cfg.moe_top_k * cfg.n_layer
    seen = {"serving_tick": 0, "serving_prefill_chunk": 0}
    for name, _, _, a in spans.spans:
        if name in seen:
            seen[name] += 1
            assert a["expert_rows"] <= a["expert_hits"] * 70
            assert a["expert_hits"] <= cfg.moe_held[1] * cfg.n_layer * 4
            assert 0.3 < a["expert_rows_share"] < 0.7
            assert 1.0 <= a["expert_load_max_over_mean"] <= cfg.moe_held[1]
            if name == "serving_prefill_chunk":
                share = a["expert_rows"] / a["expert_rows_share"]
                assert round(share) % per_layer == 0
    assert seen["serving_tick"] >= 1 and seen["serving_prefill_chunk"] == 2


# ------------------------------------------------------------ (d) the share


def test_the_shares_add_up_to_the_uncut_layer(form):
    """The share tied to the model: the routed parts that the shares
    [0, E/2) and [E/2, E) give, with the shared expert counted once, add up
    to what the uncut reference gives for the whole layer.  The two shares'
    weights are two parts of one seed's model."""
    cfg = tiny_cfg()
    E = cfg.moe_num_experts
    whole_m = dict(model_dict(cfg), moe_first_expert=0, moe_experts_held=0)
    bp = jax.tree.map(lambda a: a[0], make_params(whole_m)["blocks"])
    f = jax.random.normal(jax.random.PRNGKey(3), (2, 37, cfg.d_model))
    want = np.asarray(ref.expert_layer(bp, whole_m, f, "f32"))
    shared = np.asarray(ref.gated_mlp(
        bp["shared"]["fc1"]["kernel"], bp["shared"]["fc2"]["kernel"],
        f.reshape(-1, cfg.d_model), "f32")).reshape(f.shape)
    total, rows = shared.copy(), 0
    for first in (0, E // 2):
        part = dataclasses.replace(cfg, moe_first_expert=first,
                                   moe_experts_held=E // 2)
        pm = model_dict(part)
        pb = jax.tree.map(lambda a: a[0], make_params(pm)["blocks"])
        # this share's experts ARE those of the whole model
        np.testing.assert_array_equal(
            np.asarray(pb["moe"]["w1"]),
            np.asarray(bp["moe"]["w1"][first:first + E // 2]))
        out, _, load = lm_mod._expert_layer(pb, part, f, jnp.float32)
        total += np.asarray(out) - shared  # the shared expert once
        rows += int(np.asarray(load)[:-1].sum())
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref.expert_layer(pb, pm, f, "f32")),
            rtol=0, atol=2e-6)
    assert rows == 2 * 37 * cfg.moe_top_k  # every choice lands on one share
    # the layer's output is of size 1, where a logit's is 0.1
    np.testing.assert_allclose(total, want, rtol=0, atol=2e-6)


# ------------------------------------------------------------ (e) parts


@pytest.mark.parametrize(
    "name", MULTIPLIERS + ("fault_no_shared", "fault_gate_all",
                           "fault_capacity"))
def test_no_part_can_be_dropped(setup, name):
    """The reference with this one part changed must DISAGREE with the
    program: were the program to leave the part out, (a) would catch it.
    A multiplier is set to 1 (attention's to the usual 1 / sqrt(head_dim));
    the others are the reference's planted faults: no shared expert, the
    other gate order (a softmax over all the router's logits, the chosen k
    kept without renormalising), an expert that drops rows over a capacity."""
    cfg, m, params = setup
    ids = ids_of(48)
    got = np.asarray(lm_forward(params, cfg, jnp.asarray(ids)))
    if name in MULTIPLIERS:
        one = (1.0 / np.sqrt(m["attn_head_dim"])
               if name == "attention_multiplier" else 1.0)
        other = ref.logits_fn(params, dict(m, **{name: one}), jnp.asarray(ids))
    else:
        assert name in ref.FAULTS
        other = ref.logits_fn(params, m, jnp.asarray(ids), precision=name)
    assert np.abs(got - np.asarray(other)).max() > DROPPED


# ------------------------------------------------------------ (f) NoPE


def test_logits_do_not_move_with_rope_theta(setup):
    cfg, m, params = setup
    assert cfg.attn_rotary_dim == 0
    ids = jnp.asarray(ids_of(48))
    a = np.asarray(lm_forward(params, cfg, ids))
    b = np.asarray(lm_forward(
        params, dataclasses.replace(cfg, rope_theta=3.0), ids))
    np.testing.assert_array_equal(a, b)
    # and with a rotary embedding they would
    c = np.asarray(lm_forward(
        params, dataclasses.replace(cfg, attn_rotary_dim=-1), ids))
    assert np.abs(a - c).max() > DROPPED


# ------------------------------------------------------------ (g) loads


@pytest.mark.parametrize("to", ["one_expert", "none_to_one"])
def test_extreme_loads_are_finite_and_equal_the_reference(setup, form, to):
    """Every row to ONE held expert at once (the load no capacity holds), and
    one held expert with no row at all: finite, equal to the reference.  The
    router's columns are set by hand: inputs after the norm are positive in
    their first channel here, so a column that reads that channel alone
    decides the order."""
    cfg, m, params = setup
    bp = jax.tree.map(lambda a: a[0], params["blocks"])
    f = jnp.abs(jax.random.normal(jax.random.PRNGKey(9), (1, 40, cfg.d_model)))
    router = np.zeros((cfg.d_model, cfg.moe_num_experts), np.float32)
    if to == "one_expert":
        router[0, 2], router[0, 5], router[0, 6] = 3.0, 2.0, 1.0  # 2 is held
    else:
        router[0, 1], router[0, 5], router[0, 3] = -5.0, 2.0, 1.0  # 1 never
        router[0, 0] = 3.0
    bp = dict(bp, moe=dict(bp["moe"], router={"kernel": jnp.asarray(router)}))
    out, _, load = lm_mod._expert_layer(bp, cfg, f, jnp.float32)
    load = np.asarray(load)
    if to == "one_expert":
        assert load.tolist() == [0, 0, 40, 0, 1]
    else:
        assert load[1] == 0 and load[0] == 40 and load[3] == 40
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.expert_layer(bp, m, f, "f32")),
        rtol=0, atol=2e-6)


# ------------------------------------------------------------ operations


def test_program_and_reference_count_the_same_operations():
    """``utils/flops.py`` and the reference count the held share of the
    routed experts, the shared expert, the router whole and NoPE attention at
    its stated head width, on the toy and on the published configuration."""
    for cfg in (tiny_cfg(), get_preset("granite-4.0-h-small").model):
        m = model_dict(cfg)
        for t in (64, 1024):
            assert flops_per_token(cfg, t, training=False, convention="model") \
                == ref.forward_flops_per_token(m, t / 2, logit_positions=1.0)
            assert flops_per_token(cfg, t, training=True, convention="model") \
                == ref.train_flops_per_token(m, t)
    m = model_dict(get_preset("granite-4.0-h-small").model)
    # by hand, a token of an expert layer at the published widths: the
    # router, 10 x 36 / 72 experts of 3 x 4096 x 768 x 2, the shared expert
    assert ref.expert_layer_flops(m) == (
        2 * 4096 * 72 + 5 * 6 * 4096 * 768 + 6 * 4096 * 1536)


def test_config_refuses_a_share_outside_the_router():
    with pytest.raises(ValueError, match="held experts"):
        ModelConfig(d_intermediate=8, moe_num_experts=8, moe_first_expert=6,
                    moe_experts_held=4)
    with pytest.raises(ValueError, match="moe_shared_intermediate"):
        ModelConfig(d_intermediate=8, moe_shared_intermediate=8)
    cfg = get_preset("granite-4.0-h-small").model
    assert cfg.moe_held == (0, 36) and cfg.n_mamba_layers == 9
    assert cfg.num_params() == 4_962_732_672  # 9.93 GB in bfloat16
