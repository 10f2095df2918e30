"""Chunked-prefill tests (serving/prefill.py): planner math, one-compile
chunk-step pinning, chunked-vs-one-shot state equivalence, partial-prefill
slot residency, and engine<->generate() token parity with chunking on.

The parity tests are the contract's backbone: a LONG prompt's request
must still be bit-identical to a solo ``generate()`` call — both sides
drive the same jitted chunk step over the same chunk layout, so this is
exact, even while the engine interleaves the chunks with other slots'
decode ticks (ISSUE 3 acceptance criteria).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mamba_distributed_tpu.config import ModelConfig
from mamba_distributed_tpu.inference import generate
from mamba_distributed_tpu.inference.bucketing import pad_to_bucket
from mamba_distributed_tpu.models import init_lm_params
from mamba_distributed_tpu.models.lm import lm_prefill
from mamba_distributed_tpu.serving import (
    GenerationRequest,
    RequestStatus,
    ServingEngine,
    init_pool,
)
from mamba_distributed_tpu.serving import state_cache
from mamba_distributed_tpu.serving.prefill import (
    TRACE_COUNTS,
    cast_decode_params,
    chunk_inputs,
    chunked_prefill,
    plan_chunks,
)

pytestmark = [pytest.mark.serving, pytest.mark.fast]

# chunk = 16 tokens so a 30-50-token prompt already spans 2-4 chunks
CHUNK = 16


def tiny_cfg(layer="mamba2", **kw):
    kw.setdefault("prefill_chunk_tokens", CHUNK)
    kw.setdefault("prefill_tokens_per_tick", CHUNK)
    return ModelConfig(d_model=32, n_layer=2, vocab_size=64, ssm_layer=layer,
                       headdim=8, chunk_size=16, d_state=16,
                       compute_dtype="float32", **kw)


def rand_prompt(n, seed=1, vocab=64):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, vocab), np.int32
    )


def solo(params, cfg, prompt, key, **kw):
    out = generate(params, cfg, jnp.asarray(prompt, jnp.int32)[None], key, **kw)
    return np.asarray(out)[0, len(prompt):].tolist()


# ----------------------------------------------------------------- planner


def test_chunk_plan_math():
    assert plan_chunks(16, 16) is None  # fits one chunk -> one-shot path
    assert plan_chunks(10, 0) is None  # disabled
    plan = plan_chunks(37, 16)
    assert (plan.bucket, plan.n_chunks, plan.pad) == (48, 3, 11)
    plan = plan_chunks(32, 16)  # exact multiple: no pad
    assert (plan.bucket, plan.n_chunks, plan.pad) == (32, 2, 0)


def test_chunk_inputs_layout():
    """Pad lives entirely in chunk 0 (left, masked); later chunks are all
    real tokens — together they reassemble pad_to_bucket's layout."""
    prompt = rand_prompt(37)
    plan = plan_chunks(37, 16)
    ids = [chunk_inputs(prompt, plan, i)[0] for i in range(plan.n_chunks)]
    masks = [chunk_inputs(prompt, plan, i)[1] for i in range(plan.n_chunks)]
    joined = np.concatenate([np.asarray(x)[0] for x in ids])
    joined_mask = np.concatenate([np.asarray(m)[0] for m in masks])
    ref_ids, ref_mask = pad_to_bucket(jnp.asarray(prompt)[None], plan.bucket)
    np.testing.assert_array_equal(joined, np.asarray(ref_ids)[0])
    np.testing.assert_array_equal(joined_mask, np.asarray(ref_mask)[0])
    with pytest.raises(ValueError, match="out of range"):
        chunk_inputs(prompt, plan, 3)


def test_effective_chunk_aligns_to_ssd_boundaries():
    """mamba2 prefill chunks must land on SSD chunk boundaries: the
    effective width rounds a misaligned knob up (chunk_size is a
    sweepable perf knob, so this can't be a hard config error)."""
    assert tiny_cfg(prefill_chunk_tokens=24).effective_prefill_chunk_tokens == 32
    assert tiny_cfg(prefill_chunk_tokens=32).effective_prefill_chunk_tokens == 32
    assert tiny_cfg(prefill_chunk_tokens=0).effective_prefill_chunk_tokens == 0
    # mamba1 has no SSD chunk constraint: any width passes through
    cfg1 = tiny_cfg("mamba1", prefill_chunk_tokens=24)
    assert cfg1.effective_prefill_chunk_tokens == 24
    with pytest.raises(ValueError, match="must be >= 0"):
        tiny_cfg(prefill_chunk_tokens=-1)


# -------------------------------------------------- state equivalence


@pytest.mark.parametrize("layer", ["mamba2", "mamba1"])
def test_chunked_vs_oneshot_state_equivalence(layer):
    """Chunk-split prefill == one lm_prefill over the same padded layout,
    to fp tolerance: the carries re-associate fp32 sums at chunk
    boundaries (and XLA may tile the projections differently per
    sequence shape), but nothing drifts beyond noise.  Exactness of the
    TOKEN parity comes from both engine and generate() running the same
    chunked computation, pinned by the parity tests below."""
    cfg = tiny_cfg(layer)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    prompt = rand_prompt(37)
    plan = plan_chunks(37, CHUNK)
    padded, mask = pad_to_bucket(jnp.asarray(prompt)[None], plan.bucket)
    dparams = cast_decode_params(params, cfg=cfg)
    logits_1, state_1 = lm_prefill(dparams, cfg, padded, token_mask=mask)
    logits_c, state_c = chunked_prefill(params, cfg, prompt)
    conv_1, ssm_1 = state_1["blocks"]
    conv_c, ssm_c = state_c["blocks"]
    np.testing.assert_allclose(
        np.asarray(conv_c), np.asarray(conv_1), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(ssm_c), np.asarray(ssm_1), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(logits_c), np.asarray(logits_1), rtol=1e-5, atol=1e-5
    )


# ---------------------------------------------------------- trace pinning


def test_chunk_step_traces_once():
    """The chunk step compiles ONCE per (model config, chunk size): any
    mix of long prompt lengths reuses it, and generate()'s chunked path
    adds one decode trace — never a per-length prefill trace."""
    from mamba_distributed_tpu.inference.generate import (
        TRACE_COUNTS as GEN_TRACES,
    )

    # own model shape so the jit cache can't already hold the signature
    cfg = ModelConfig(d_model=16, n_layer=2, vocab_size=32, ssm_layer="mamba2",
                      headdim=4, chunk_size=8, d_state=8,
                      compute_dtype="float32", prefill_chunk_tokens=8)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    key = jax.random.PRNGKey(0)
    c0, g0, d0 = (TRACE_COUNTS["chunk"], GEN_TRACES["generate"],
                  GEN_TRACES["decode"])
    for t in (9, 13, 24, 31):  # 2-4 chunks each
        generate(params, cfg, jnp.ones((1, t), jnp.int32), key,
                 max_new_tokens=3, top_k=16)
    assert TRACE_COUNTS["chunk"] == c0 + 1
    assert GEN_TRACES["decode"] == d0 + 1
    assert GEN_TRACES["generate"] == g0  # the one-shot impl never ran


def test_engine_chunked_prefill_traces_once():
    """Engine side of the same pin: long prompts of different lengths
    share the one chunk-step compile; the tick still traces once."""
    from mamba_distributed_tpu.serving.engine import (
        TRACE_COUNTS as ENG_TRACES,
    )

    cfg = ModelConfig(d_model=16, n_layer=3, vocab_size=32, ssm_layer="mamba2",
                      headdim=4, chunk_size=8, d_state=8,
                      compute_dtype="float32", prefill_chunk_tokens=8,
                      prefill_tokens_per_tick=8)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2,
                        max_top_k=20)
    c0, t0 = TRACE_COUNTS["chunk"], ENG_TRACES["tick"]
    reqs = [GenerationRequest(prompt_ids=rand_prompt(n, seed=n, vocab=32),
                              top_k=20, max_new_tokens=3,
                              key=jax.random.PRNGKey(n))
            for n in (9, 14, 22, 17)]
    eng.run(reqs)
    assert TRACE_COUNTS["chunk"] == c0 + 1
    assert ENG_TRACES["tick"] == t0 + 1


# ----------------------------------------------------------- engine parity


@pytest.mark.parametrize("layer", ["mamba2", "mamba1"])
def test_engine_chunked_single_request_parity(layer):
    """A chunked-prefill request's tokens are bit-identical to solo
    generate() with the same key (which runs the same chunk step)."""
    cfg = tiny_cfg(layer)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    prompt = rand_prompt(53)
    key = jax.random.PRNGKey(7)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2)
    res = eng.run([GenerationRequest(prompt_ids=prompt, max_new_tokens=7,
                                     temperature=0.9, key=key)])[0]
    assert res.finish_reason == "length"
    assert res.new_tokens.tolist() == solo(
        params, cfg, prompt, key, max_new_tokens=7, temperature=0.9
    )
    s = eng.metrics.summary()
    assert s["prefill_chunks"] == plan_chunks(53, CHUNK).n_chunks


def test_interleaved_chunked_admit_evict_parity():
    """The acceptance scenario: a long prompt streams in chunk-by-chunk
    WHILE other slots decode, finish, and a new request takes a freed
    slot — every stream still matches its solo generate() run, and the
    budget forces the prefill to span multiple ticks."""
    cfg = tiny_cfg()  # budget 16 == one chunk per tick
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    keys = {n: jax.random.PRNGKey(30 + i) for i, n in enumerate("LAB")}
    prompts = {"L": rand_prompt(53), "A": rand_prompt(5, seed=2),
               "B": rand_prompt(7, seed=3)}
    budgets = {"L": 5, "A": 4, "B": 6}

    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=1)
    ids = {}
    ids["A"] = eng.submit(GenerationRequest(
        prompt_ids=prompts["A"], max_new_tokens=budgets["A"], key=keys["A"]))
    eng.step()  # A decoding alone
    ids["L"] = eng.submit(GenerationRequest(
        prompt_ids=prompts["L"], max_new_tokens=budgets["L"], key=keys["L"]))
    eng.step()  # L admitted: first chunk in, A still decoding
    tracked_L = eng._slots[[s for s, t in eng._slots.items()
                            if t.request_id == ids["L"]][0]]
    assert tracked_L.status is RequestStatus.PREFILL  # mid-prefill residency
    assert 0 < tracked_L.chunks_done < tracked_L.plan.n_chunks
    ids["B"] = eng.submit(GenerationRequest(
        prompt_ids=prompts["B"], max_new_tokens=budgets["B"], key=keys["B"]))
    # capacity 2: B waits for A's slot while L is still mid-prefill
    assert eng.scheduler.depth == 1
    while eng.pending:
        eng.step()
    for name in "LAB":
        got = eng.results[ids[name]].new_tokens.tolist()
        want = solo(params, cfg, prompts[name], keys[name],
                    max_new_tokens=budgets[name])
        assert got == want, f"request {name} diverged: {got} vs {want}"


def test_prefill_budget_paces_chunks():
    """prefill_tokens_per_tick=chunk => exactly one chunk per step, so an
    n-chunk prompt's prefill spans n steps; 0 (unbounded) does it all
    before the first tick."""
    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    prompt = rand_prompt(53)  # 4 chunks
    n_chunks = plan_chunks(53, CHUNK).n_chunks

    eng = ServingEngine(params, cfg, capacity=1, tokens_per_tick=2)
    eng.submit(GenerationRequest(prompt_ids=prompt, max_new_tokens=3,
                                 key=jax.random.PRNGKey(0)))
    per_step = []
    while eng.pending:
        before = eng.metrics.prefill_chunks
        eng.step()
        per_step.append(eng.metrics.prefill_chunks - before)
    assert per_step[:n_chunks] == [1] * n_chunks  # one chunk per grant

    eng = ServingEngine(params, cfg, capacity=1, tokens_per_tick=2,
                        prefill_tokens_per_tick=0)  # unbounded
    eng.submit(GenerationRequest(prompt_ids=prompt, max_new_tokens=3,
                                 key=jax.random.PRNGKey(0)))
    eng.step()
    assert eng.metrics.prefill_chunks == n_chunks  # all before the tick
    s = eng.metrics.summary()
    assert s["prefill_chunk_tokens"] == n_chunks * CHUNK
    assert s["prefill_stall_ms"]["count"] >= 1


def test_tickless_steps_roll_accounting_into_next_tick_record(tmp_path):
    """A lone long request produces tick-less prefill-only steps; their
    chunk tokens and stall must still reach the serving_tick jsonl
    stream (rolled into the next tick's record), so obs_report totals
    match ServingMetrics exactly."""
    import json

    from mamba_distributed_tpu.utils.metrics import ServingMetrics

    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    jsonl = tmp_path / "ticks.jsonl"
    metrics = ServingMetrics(capacity=1, jsonl_path=str(jsonl))
    eng = ServingEngine(params, cfg, capacity=1, tokens_per_tick=2,
                        metrics=metrics)
    eng.run([GenerationRequest(prompt_ids=rand_prompt(53), max_new_tokens=3,
                               key=jax.random.PRNGKey(0))])
    ticks = [json.loads(ln) for ln in open(jsonl)
             if json.loads(ln)["kind"] == "serving_tick"]
    plan = plan_chunks(53, CHUNK)
    assert sum(t["prefill_chunk_tokens"] for t in ticks) == plan.bucket
    assert sum(t["prefill_stall_ms"] for t in ticks) > 0
    assert sum(t["prefill_chunk_ms"] for t in ticks) > 0


# ------------------------------------------------ partial-prefill residency


def test_stash_survives_tick():
    """A stashed carry must come through a decode tick bit-identical —
    the tick's lm_step writes are masked for prefilling slots."""
    from mamba_distributed_tpu.serving import engine as engine_mod

    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    dparams = cast_decode_params(params, cfg=cfg)
    pool = init_pool(cfg, capacity=2)
    # slot 0: a real decodable request
    logits, state = lm_prefill(dparams, cfg, jnp.ones((1, 8), jnp.int32))
    pool = state_cache.insert(pool, 0, state, logits, jax.random.PRNGKey(0),
                              8, 5, 1.0, -1)
    # slot 1: a partial carry (chunk 1 of a longer prompt)
    prompt = rand_prompt(40)
    plan = plan_chunks(40, CHUNK)
    from mamba_distributed_tpu.models.lm import init_lm_state
    from mamba_distributed_tpu.serving.prefill import prefill_chunk

    ids, mask = chunk_inputs(prompt, plan, 0)
    _, carry = prefill_chunk(dparams, ids, mask, init_lm_state(cfg, 1),
                             cfg=cfg)
    pool = state_cache.stash_prefill(pool, 1, carry, jax.random.PRNGKey(1),
                                     8, 5, 1.0, -1)
    assert np.asarray(pool["meta"]["prefilling"]).tolist() == [False, True]
    before = [np.asarray(x) for x in jax.tree.leaves(
        state_cache.read_state(pool, 1))]
    pool, tokens, emitted, done, _ = engine_mod._tick(
        dparams, pool, cfg=cfg, k_max=5, steps=3
    )
    # slot 0 decoded, slot 1 emitted nothing and its carry is untouched
    assert np.asarray(emitted)[:, 0].all()
    assert not np.asarray(emitted)[:, 1].any()
    after = [np.asarray(x) for x in jax.tree.leaves(
        state_cache.read_state(pool, 1))]
    for b, a in zip(before, after):
        np.testing.assert_array_equal(b, a)
    # finish flips the slot decodable
    pool = state_cache.finish_prefill(pool, 1, carry,
                                      jnp.zeros((1, cfg.vocab_size_padded)))
    assert np.asarray(pool["meta"]["prefilling"]).tolist() == [False, False]
    assert np.asarray(pool["meta"]["active"]).tolist() == [True, True]


_HYBRID = dict(attn_num_heads=4, attn_num_kv_heads=2, kv_page_tokens=8,
               kv_slot_tokens=64)


@pytest.mark.parametrize("layer,extra", [
    ("mamba2", {}),
    ("mamba1", {}),
    ("mamba2", dict(n_layer=6, attn_layer_idx=(1, 4), **_HYBRID)),  # periodic
    ("mamba1", dict(n_layer=6, attn_layer_idx=(1, 4), **_HYBRID)),
    ("mamba2", dict(n_layer=4, attn_layer_idx=(0, 3), **_HYBRID)),  # unrolled
], ids=["mamba2", "mamba1", "mamba2-hybrid", "mamba1-hybrid",
        "mamba2-hybrid-aperiodic"])
def test_lm_step_state_mask_holds_rows(layer, extra):
    """``lm_step(state_mask=)``: a held row's conv + SSM carry comes back
    bit-equal to the input; every other row's carry and logits are the
    unmasked call's, bit for bit — on each layer-loop path.

    Mamba-1's update adds two elementwise products, and the CPU backend
    contracts one of them into the add — WHICH one depends on the fusion
    around them, so its compiled masked and unmasked programs differ in
    the last bit.  Its compiled run is held to that, and the bit-for-bit
    claim is checked op by op (``disable_jit``), where nothing contracts.
    Mamba-2's single elementwise product leaves no choice: exact compiled."""
    from mamba_distributed_tpu.models.lm import init_lm_state, lm_step

    cfg = dataclasses.replace(tiny_cfg(layer), **extra)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    state = init_lm_state(cfg, 3, 16)
    for i in range(2):  # a non-trivial carry in every row
        _, state = lm_step(params, cfg, state,
                           jnp.asarray([3 + i, 9 + i, 27 + i], jnp.int32))
    tok = jnp.asarray([5, 11, 41], jnp.int32)
    mask = np.array([True, False, True])
    leaves = lambda st: [np.asarray(x) for x in jax.tree.leaves(st["blocks"])]

    def check(same):
        ref_logits, ref = lm_step(params, cfg, state, tok)
        logits, got = lm_step(params, cfg, state, tok,
                              state_mask=jnp.asarray(mask))
        same(np.asarray(logits)[mask], np.asarray(ref_logits)[mask])
        for old, new, want in zip(leaves(state), leaves(got), leaves(ref)):
            assert new.dtype == old.dtype and new.shape == old.shape
            np.testing.assert_array_equal(new[:, ~mask], old[:, ~mask])
            same(new[:, mask], want[:, mask])
            assert not np.array_equal(want[:, ~mask], old[:, ~mask])
        # all True is None: the values generate()'s decode loop computes
        _, full = lm_step(params, cfg, state, tok,
                          state_mask=jnp.ones((3,), bool))
        for new, want in zip(leaves(full), leaves(ref)):
            same(new, want)

    if layer == "mamba2":
        check(np.testing.assert_array_equal)
    else:
        check(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5,
                                                      atol=1e-7))
        with jax.disable_jit():
            check(np.testing.assert_array_equal)


def test_parked_slot_resumes_to_solo_stream():
    """A slot parked mid-prefill over several ticks — beside a live
    neighbour and a slot that finishes and goes dead inside those ticks
    — resumes to the stream of a solo generate() that was never parked,
    and so do the neighbours."""
    cfg = tiny_cfg()  # budget 16 == one chunk a step
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    prompts = {"live": rand_prompt(6, seed=2), "dead": rand_prompt(4, seed=3),
               "long": rand_prompt(75, seed=4)}
    budgets = {"live": 14, "dead": 2, "long": 6}
    keys = {n: jax.random.PRNGKey(50 + i) for i, n in enumerate(prompts)}
    eng = ServingEngine(params, cfg, capacity=3, tokens_per_tick=3)
    ids = {n: eng.submit(GenerationRequest(
        prompt_ids=prompts[n], max_new_tokens=budgets[n], key=keys[n]))
        for n in ("live", "dead", "long")}
    parked_ticks = 0
    while eng.pending:
        eng.step()
        long_ = [t for t in eng._slots.values()
                 if t.request_id == ids["long"]]
        if long_ and long_[0].status is RequestStatus.PREFILL:
            # a tick ran over the parked carry, with the live slot
            # decoding and the dead one done (then evicted: empty)
            assert 0 < long_[0].chunks_done < long_[0].plan.n_chunks
            assert ids["live"] in {t.request_id for t in eng._slots.values()}
            parked_ticks += 1
    assert parked_ticks >= 3
    assert len(eng.results[ids["dead"]].new_tokens) == 2
    for name in prompts:
        got = eng.results[ids[name]].new_tokens.tolist()
        want = solo(params, cfg, prompts[name], keys[name],
                    max_new_tokens=budgets[name])
        assert got == want, f"request {name} diverged: {got} vs {want}"


def _walk_eqns(jaxpr):
    """``(equation, wraps)`` for every equation of a jaxpr and of the jaxprs
    nested in its params; ``wraps`` says the equation has such a body
    (scan, pjit, cond, ...), whose equations follow.  A ``pallas_call`` is
    one equation: its body is the kernel's."""
    for eqn in jaxpr.eqns:
        subs = [] if eqn.primitive.name == "pallas_call" else [
            getattr(sub, "jaxpr", sub)
            for v in eqn.params.values()
            for sub in (v if isinstance(v, (tuple, list)) else (v,))
            if hasattr(getattr(sub, "jaxpr", sub), "eqns")
        ]
        yield eqn, bool(subs)
        for sub in subs:
            yield from _walk_eqns(sub)


@pytest.mark.parametrize("program,attn_impl", [
    ("tick", None),              # pure SSM (PR 26's case)
    ("tick", "pallas"),          # periodic hybrid, kernels interpreted
    ("tick", "xla"),             # periodic hybrid, the lax fallback
    ("prefill_chunk", "pallas"),
    ("prefill_chunk", "xla"),
])
def test_tick_updates_the_pool_in_place(program, attn_impl):
    """Structure of the serving programs, by jaxpr.  The layer loops (the
    pure-SSM layer scan; the periodic hybrid's group scan and the Mamba
    scans inside a group) hold the stacked state in their CARRY and never
    among their scanned inputs or outputs: either is a second pool-sized
    buffer on the device.  That is every leaf of ``state["blocks"]`` in
    the tick and every leaf of the page pool ``state["attn_blocks"]`` in
    the hybrid tick and chunk step.  Outside a ``pallas_call`` nothing but
    an in-place write (``scatter`` / ``dynamic_update_slice``) produces an
    array of a pool leaf's shape, no ``select_n`` and no ``concatenate``
    among them; and where the kernels walk the pages nothing produces an
    array of one layer's page slice ``(P, nkv, pg, hd)`` either (the lax
    fallback gathers its pages out of the pool by index)."""
    from mamba_distributed_tpu.serving import engine as engine_mod

    S, steps = 5, 4  # no layer loop is 4 long
    if attn_impl is None:
        cfg = dataclasses.replace(tiny_cfg(), n_layer=3)
    else:
        # two groups of [mamba, attn, mamba]: period 3, offset 1
        cfg = dataclasses.replace(
            tiny_cfg(attn_layer_idx=(1, 4), attn_num_heads=4,
                     attn_num_kv_heads=2, remat=False, kv_page_tokens=8,
                     kv_slot_tokens=32, attn_impl=attn_impl),
            n_layer=6)
    dparams = cast_decode_params(
        init_lm_params(jax.random.PRNGKey(0), cfg), cfg=cfg)
    pool = init_pool(cfg, capacity=S)
    if program == "tick":
        state = pool["state"]
        kw = ({} if attn_impl is None else dict(
            tbl=jnp.zeros((S, 4), jnp.int32), lengths=jnp.zeros((S,), jnp.int32)
        ))
        jaxpr = jax.make_jaxpr(
            lambda p, q: engine_mod._tick(p, q, cfg=cfg, k_max=5,
                                          steps=steps, **kw)
        )(dparams, pool)
        carried = jax.tree.leaves(state)
    else:
        # the engine's chunk carry: one request's Mamba state, the whole
        # page pool, that slot's table row and length
        state = {
            **state_cache.read_state(pool, 0),
            "attn_blocks": pool["state"]["attn_blocks"],
            "attn_meta": (jnp.zeros((1, 4), jnp.int32),
                          jnp.zeros((1,), jnp.int32)),
        }
        jaxpr = jax.make_jaxpr(
            lambda p, st: engine_mod.prefill_chunk(
                p, jnp.zeros((1, 16), jnp.int32), jnp.ones((1, 16)), st,
                cfg=cfg)
        )(dparams, state)
        carried = jax.tree.leaves(state["attn_blocks"])
    pool_shapes = {x.shape for x in carried}
    n_groups = len(cfg.attn_layer_idx) or cfg.n_layer
    assert all(s[0] in (cfg.n_layer - len(cfg.attn_layer_idx),
                        len(cfg.attn_layer_idx)) for s in pool_shapes)
    slice_shapes = (
        {x.shape[1:] for x in jax.tree.leaves(state["attn_blocks"])
         if x.ndim == 5}
        if attn_impl == "pallas" else set()
    )
    assert (attn_impl == "pallas") == bool(slice_shapes)

    shape = lambda v: getattr(v.aval, "shape", None)
    layer_loops = pallas_calls = 0
    for eqn, wraps in _walk_eqns(jaxpr.jaxpr):
        name = eqn.primitive.name
        if name == "pallas_call":
            pallas_calls += 1
            continue
        if name == "scan" and eqn.params["length"] != steps:
            # a layer loop: whatever of the stacked state it touches, it
            # carries (the sub-step scan, of length `steps`, carries the
            # whole pool by construction and is not one)
            nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
            carry = {shape(v) for v in eqn.invars[nc:nc + nk]}
            scanned = ({shape(v) for v in eqn.invars[nc + nk:]}
                       | {shape(v) for v in eqn.outvars[nk:]})
            assert not pool_shapes & scanned, eqn
            if eqn.params["length"] == n_groups:
                layer_loops += 1
                assert pool_shapes <= carry
        if wraps:
            continue
        for out in eqn.outvars:
            if shape(out) in pool_shapes:
                assert name in ("scatter", "dynamic_update_slice"), eqn
            assert shape(out) not in slice_shapes, eqn
    assert layer_loops == 1
    assert pallas_calls == (attn_impl == "pallas")


@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
def test_periodic_and_unrolled_hybrid_agree(monkeypatch, attn_impl):
    """The group scan (periodic hybrids) and the unrolled loop (aperiodic
    ones) run the same indexed layer functions over the same carried
    state: the same stack taken down either branch gives the same logits
    and the same state, through two chunks of ``lm_prefill_chunk`` and
    three steps of ``lm_step``."""
    from mamba_distributed_tpu.models import lm
    from mamba_distributed_tpu.models.lm import (
        init_lm_state, lm_prefill_chunk, lm_step,
    )

    cfg = dataclasses.replace(
        tiny_cfg(attn_layer_idx=(1, 4), attn_num_heads=4,
                 attn_num_kv_heads=2, remat=False, kv_page_tokens=8,
                 kv_slot_tokens=64, attn_impl=attn_impl),
        n_layer=6)
    dparams = cast_decode_params(
        init_lm_params(jax.random.PRNGKey(0), cfg), cfg=cfg)
    b = 2
    ids = jnp.asarray(np.stack([rand_prompt(2 * CHUNK, seed=s)
                                for s in (1, 2)]))
    mask = jnp.ones((b, CHUNK)).at[1, :5].set(0.0)  # a left pad, chunk 0

    def run():
        state = init_lm_state(cfg, b, 64)
        state["attn_meta"] = (state["attn_meta"][0],
                              jnp.zeros((b,), jnp.int32))
        outs = []
        for i in range(2):
            logits, state = lm_prefill_chunk(
                dparams, cfg, ids[:, i * CHUNK:(i + 1) * CHUNK], state,
                token_mask=mask if i == 0 else None)
            outs.append(logits)
        for i in range(3):
            tok = jnp.argmax(logits[:, :cfg.vocab_size], axis=-1)
            logits, state = lm_step(
                dparams, cfg, state, tok.astype(jnp.int32),
                write_mask=jnp.asarray([True, i != 1]))
            outs.append(logits)
        return outs, state

    assert lm._hybrid_period(cfg) == (3, 1)
    scanned, scanned_state = run()
    monkeypatch.setattr(lm, "_hybrid_period", lambda cfg: None)
    unrolled, unrolled_state = run()
    for a, c in zip(scanned, unrolled):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=0, atol=1e-5)
    # page 0 of a layer is the trash page: garbage by contract
    trash = lambda st: {**st, "attn_blocks": jax.tree.map(
        lambda x: x[:, 1:], st["attn_blocks"])}
    for a, c in zip(jax.tree.leaves(trash(scanned_state)),
                    jax.tree.leaves(trash(unrolled_state))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=0, atol=1e-5)


def test_failed_chunk_requeues_and_frees_slot(monkeypatch):
    """A chunk step that raises mid-prefill must free the slot, evict the
    stash, and requeue the request from chunk 0 (same contract as the
    one-shot prefill failure path)."""
    from mamba_distributed_tpu.serving import engine as engine_mod

    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, capacity=1, tokens_per_tick=2)
    rid = eng.submit(GenerationRequest(prompt_ids=rand_prompt(40),
                                       max_new_tokens=4,
                                       key=jax.random.PRNGKey(0)))
    real = engine_mod.prefill_chunk
    monkeypatch.setattr(engine_mod, "prefill_chunk",
                        lambda *a, **k: (_ for _ in ()).throw(
                            RuntimeError("boom")))
    with pytest.raises(RuntimeError, match="boom"):
        eng.step()
    assert eng.pending == 1 and eng.scheduler.depth == 1  # not dropped
    assert eng._free == [0] and eng._prefill_queue == []  # slot reclaimed
    monkeypatch.setattr(engine_mod, "prefill_chunk", real)
    while eng.pending:
        eng.step()
    assert len(eng.results[rid].new_tokens) == 4  # served after recovery


def test_chunk_step_gets_copies_of_the_host_kv_mirrors(monkeypatch):
    """The hybrid chunk step is dispatched asynchronously (and donates
    its state) while the host's KV-length mirror advances right after.
    On the CPU backend ``jnp.asarray`` ALIASES a 64-byte-aligned host
    buffer, so a view of the mirror handed to the step shares memory
    with it: a queued step reads the advanced length — wrong tokens
    whenever numpy happens to allocate the mirror aligned.  Pin, with
    the mirrors forced aligned, that what the step gets is no alias."""
    from mamba_distributed_tpu.serving import engine as engine_mod

    def aligned_like(arr):
        raw = np.zeros(arr.nbytes + 128, np.uint8)
        start = (-raw.ctypes.data) % 64
        out = raw[start:start + arr.nbytes].view(arr.dtype)
        return out.reshape(arr.shape)

    cfg = tiny_cfg(attn_layer_idx=(1,), attn_num_heads=4,
                   attn_num_kv_heads=2, remat=False, kv_page_tokens=8,
                   kv_slot_tokens=64)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2)
    eng._kv_len = aligned_like(eng._kv_len)
    eng._page_tbl = aligned_like(eng._page_tbl)

    aliased = []
    real = engine_mod.prefill_chunk

    def spy(params, ids, mask, state, **kw):
        tbl, lens = state["attn_meta"]
        aliased.append((
            tbl.unsafe_buffer_pointer() == eng._page_tbl.ctypes.data,
            lens.unsafe_buffer_pointer() == eng._kv_len.ctypes.data,
        ))
        return real(params, ids, mask, state, **kw)

    monkeypatch.setattr(engine_mod, "prefill_chunk", spy)
    prompt, key = rand_prompt(40), jax.random.PRNGKey(3)
    rid = eng.submit(GenerationRequest(prompt_ids=prompt, max_new_tokens=4,
                                       key=key))  # lands in slot 0
    while eng.pending:
        eng.step()
    assert aliased == [(False, False)] * 3  # 48 padded tokens, 3 chunks
    assert eng.results[rid].new_tokens.tolist() == solo(
        params, cfg, prompt, key, max_new_tokens=4)


# ------------------------------------------------------------- satellites


def test_hybrid_requests_always_plan_chunks():
    """Hybrid prompts of ANY length take the chunk path (force=True):
    it is the one prefill that masks pad keys (never written to pages)
    and writes straight into the slot's pool pages."""
    assert plan_chunks(5, 16) is None          # short pure-SSM: one-shot
    plan = plan_chunks(5, 16, force=True)      # short hybrid: 1 chunk
    assert (plan.bucket, plan.n_chunks, plan.pad) == (16, 1, 11)
    assert plan_chunks(5, 0, force=True) is None  # chunking off: no plan


def test_chunking_disabled_reproduces_oneshot_streams():
    """prefill_chunk_tokens=0 must reproduce the pre-chunking pow2 path
    exactly (the opt-out knob)."""
    cfg_on = tiny_cfg()
    cfg_off = dataclasses.replace(cfg_on, prefill_chunk_tokens=0)
    params = init_lm_params(jax.random.PRNGKey(0), cfg_on)
    prompt = rand_prompt(53)
    key = jax.random.PRNGKey(3)
    on = solo(params, cfg_on, prompt, key, max_new_tokens=6)
    off = solo(params, cfg_off, prompt, key, max_new_tokens=6)
    # different prefill layouts (48-bucket chunked vs 64-bucket one-shot)
    # sample the same stream here because the fp noise between them is
    # far below sampling resolution; the engine matches whichever layout
    # its cfg selects
    assert on == off
    eng = ServingEngine(params, cfg_off, capacity=1, tokens_per_tick=2)
    res = eng.run([GenerationRequest(prompt_ids=prompt, max_new_tokens=6,
                                     key=key)])[0]
    assert res.new_tokens.tolist() == off
    assert eng.metrics.prefill_chunks == 0  # never chunked


def test_budget_round_robins_across_concurrent_longs():
    """Two long prompts in flight split the per-tick chunk budget
    round-robin (satellite: the ROADMAP PR-3 refinement) — with a
    one-chunk budget they alternate grants instead of FCFS-draining the
    older prompt first, so neither starves the other's TTFT."""
    cfg = tiny_cfg()  # budget 16 == one chunk per step
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=1)
    r1 = eng.submit(GenerationRequest(prompt_ids=rand_prompt(53, seed=1),
                                      max_new_tokens=3,
                                      key=jax.random.PRNGKey(0)))
    r2 = eng.submit(GenerationRequest(prompt_ids=rand_prompt(53, seed=2),
                                      max_new_tokens=3,
                                      key=jax.random.PRNGKey(1)))
    by_rid = {}
    eng.step()  # both admitted; ONE chunk granted (to r1)
    by_rid = {t.request_id: t for t in eng._slots.values()}
    assert by_rid[r1].chunks_done == 1 and by_rid[r2].chunks_done == 0
    eng.step()  # next grant goes to r2, not r1 (rotation)
    assert by_rid[r2].chunks_done == 1
    assert abs(by_rid[r1].chunks_done - by_rid[r2].chunks_done) <= 1
    eng.step()
    eng.step()
    # after 4 single-chunk grants the split is 2/2 — FCFS would be 4/0
    assert (by_rid[r1].chunks_done, by_rid[r2].chunks_done) == (2, 2)
    # streams still match solo generate() exactly
    while eng.pending:
        eng.step()
    for rid, seed, key in ((r1, 1, 0), (r2, 2, 1)):
        want = solo(params, cfg, rand_prompt(53, seed=seed),
                    jax.random.PRNGKey(key), max_new_tokens=3)
        assert eng.results[rid].new_tokens.tolist() == want


def test_srpt_nearly_done_prompt_finishes_before_fresh_long():
    """``prefill_schedule="srpt"``: a prompt with one chunk left gets the
    remaining grants ahead of a freshly-admitted much longer prompt —
    the nearly-done request reaches its first token while the fresh one
    hasn't prefilled a single chunk (round-robin would alternate and
    delay it; the PR-5 SRPT satellite)."""
    cfg = tiny_cfg(prefill_schedule="srpt")  # budget 16 == 1 grant/step
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=1)
    ra = eng.submit(GenerationRequest(prompt_ids=rand_prompt(53, seed=1),
                                      max_new_tokens=3,
                                      key=jax.random.PRNGKey(0)))
    eng.step()
    eng.step()  # A (4 chunks) now has 2 done, 2 remaining
    by_rid = {t.request_id: t for t in eng._slots.values()}
    assert by_rid[ra].chunks_done == 2
    rb = eng.submit(GenerationRequest(prompt_ids=rand_prompt(128, seed=2),
                                      max_new_tokens=3,
                                      key=jax.random.PRNGKey(1)))
    # A's 2 remaining grants outrank B's fresh 8: A streams its first
    # token before B has prefilled ANYTHING
    events = []
    while not any(ev.request_id == ra for ev in events):
        events = eng.step()
        by_rid.update({t.request_id: t for t in eng._slots.values()})
    assert by_rid[rb].chunks_done == 0
    while eng.pending:
        eng.step()
    for rid, n, seed, key in ((ra, 53, 1, 0), (rb, 128, 2, 1)):
        want = solo(params, cfg, rand_prompt(n, seed=seed),
                    jax.random.PRNGKey(key), max_new_tokens=3)
        assert eng.results[rid].new_tokens.tolist() == want


def test_srpt_starvation_guard_grants_passed_over_prompt():
    """A long prompt passed over ``SRPT_STARVATION_GRANTS`` times in a
    row takes the next grant even when a shorter prefill is resident —
    a stream of short arrivals can't starve it indefinitely."""
    cfg = tiny_cfg(prefill_schedule="srpt")
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, capacity=4, tokens_per_tick=1)
    assert eng.SRPT_STARVATION_GRANTS == 4
    ra = eng.submit(GenerationRequest(prompt_ids=rand_prompt(128, seed=1),
                                      max_new_tokens=2,
                                      key=jax.random.PRNGKey(0)))
    eng.step()  # A admitted alone: first grant is its
    shorts = [eng.submit(GenerationRequest(
        prompt_ids=rand_prompt(21, seed=10 + i), max_new_tokens=2,
        key=jax.random.PRNGKey(10 + i))) for i in range(2)]
    by_rid = {t.request_id: t for t in eng._slots.values()}
    for _ in range(4):  # S1,S1,S2,S2 — A passed over four times
        eng.step()
        by_rid.update({t.request_id: t for t in eng._slots.values()})
    assert by_rid[ra].chunks_done == 1
    assert by_rid[ra].prefill_skipped == 4
    assert all(by_rid[s].chunks_done == 2 for s in shorts)
    # a FRESH short arrives — SRPT alone would grant it (2 remaining vs
    # A's 7), but A is starved, so A takes the grant
    rc = eng.submit(GenerationRequest(prompt_ids=rand_prompt(21, seed=30),
                                      max_new_tokens=2,
                                      key=jax.random.PRNGKey(30)))
    eng.step()
    by_rid.update({t.request_id: t for t in eng._slots.values()})
    assert by_rid[ra].chunks_done == 2
    assert by_rid[ra].prefill_skipped == 0
    assert by_rid[rc].chunks_done == 0
    while eng.pending:
        eng.step()
    want = solo(params, cfg, rand_prompt(128, seed=1),
                jax.random.PRNGKey(0), max_new_tokens=2)
    assert eng.results[ra].new_tokens.tolist() == want
