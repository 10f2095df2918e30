"""Serving-engine tests: slot-pool mechanics, generate() parity, tracing.

The parity tests are the subsystem's backbone: a request's tokens must be
bit-identical to a solo ``generate()`` call with the same key no matter
what admissions/evictions happen around it in the pool.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mamba_distributed_tpu.config import ModelConfig
from mamba_distributed_tpu.inference import generate, next_pow2_bucket, pad_to_bucket
from mamba_distributed_tpu.models import init_lm_params
from mamba_distributed_tpu.serving import (
    GenerationRequest,
    ServingEngine,
    init_pool,
    insert,
)
from mamba_distributed_tpu.serving import state_cache

pytestmark = [pytest.mark.serving, pytest.mark.fast]


def tiny_cfg(layer="mamba2"):
    return ModelConfig(d_model=32, n_layer=2, vocab_size=64, ssm_layer=layer,
                       headdim=8, chunk_size=16, d_state=16,
                       compute_dtype="float32")


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def solo(params, cfg, prompt, key, **kw):
    """Reference: batch-1 generate(), returning just the generated suffix."""
    out = generate(params, cfg, jnp.asarray(prompt, jnp.int32)[None], key, **kw)
    return np.asarray(out)[0, len(prompt):].tolist()


# ---------------------------------------------------------------- slot pool


def test_insert_writes_one_slot(setup):
    cfg, params = setup
    pool = init_pool(cfg, capacity=3)
    from mamba_distributed_tpu.models.lm import lm_prefill

    prompt = jnp.ones((1, 8), jnp.int32)
    logits, state = lm_prefill(params, cfg, prompt)
    pool = insert(pool, 1, state, logits, jax.random.PRNGKey(3), 5, 7, 0.5, 42)
    meta = pool["meta"]
    assert np.asarray(meta["active"]).tolist() == [False, True, False]
    assert int(meta["max_new"][1]) == 5 and int(meta["top_k"][1]) == 7
    assert float(meta["temperature"][1]) == 0.5 and int(meta["eos_id"][1]) == 42
    np.testing.assert_array_equal(
        np.asarray(pool["logits"][1]), np.asarray(logits[0])
    )
    # the written slot's state rows match the prefill state; others untouched
    for pl, nl in zip(jax.tree.leaves(pool["state"]), jax.tree.leaves(state)):
        np.testing.assert_array_equal(np.asarray(pl[:, 1]), np.asarray(nl[:, 0]))
        assert not np.asarray(pl[:, 0]).any() and not np.asarray(pl[:, 2]).any()


def test_evict_frees_slot_only(setup):
    cfg, params = setup
    pool = init_pool(cfg, capacity=2)
    from mamba_distributed_tpu.models.lm import lm_prefill

    logits, state = lm_prefill(params, cfg, jnp.ones((1, 8), jnp.int32))
    pool = insert(pool, 0, state, logits, jax.random.PRNGKey(0), 4, 1, 1.0, -1)
    pool = insert(pool, 1, state, logits, jax.random.PRNGKey(1), 4, 1, 1.0, -1)
    pool = state_cache.evict(pool, 0)
    assert np.asarray(pool["meta"]["active"]).tolist() == [False, True]


def test_pool_admits_hybrid_with_paged_kv():
    """Hybrid configs build a pool whose attention KV is a PAGE pool
    (per-layer HEAD-MAJOR (P, nkv, page, hd) arrays, page 0 reserved as
    trash) — the ragged/paged-attention pattern that unlocked hybrid
    serving, stored kernel-native so the Pallas page walk needs no
    transpose."""
    cfg = ModelConfig(d_model=32, n_layer=2, vocab_size=64, ssm_layer="mamba2",
                      headdim=8, chunk_size=16, d_state=16,
                      compute_dtype="float32", attn_layer_idx=(1,),
                      attn_num_heads=4, attn_num_kv_heads=2, remat=False,
                      prefill_chunk_tokens=16, kv_page_tokens=8,
                      kv_slot_tokens=64)
    pool = init_pool(cfg, capacity=2)
    k_pages, v_pages = pool["state"]["attn_blocks"]
    n_pages = state_cache.hybrid_pool_pages(cfg, 2)   # 2 slots * 8 pages
    assert n_pages == 16
    assert k_pages.shape == (1, n_pages + 1, 2, 8, 8)  # (A, P+trash, nkv, pg, hd)
    assert v_pages.shape == k_pages.shape
    # hybrid serving requires the chunk path (it writes the pages)
    import dataclasses
    with pytest.raises(ValueError, match="chunked prefill"):
        init_pool(dataclasses.replace(cfg, prefill_chunk_tokens=0), 2)


# -------------------------------------------------------------- engine parity


@pytest.mark.parametrize("layer", ["mamba2", "mamba1"])
def test_single_request_parity(layer):
    """Token-for-token identical to a solo generate() with the same key."""
    cfg = tiny_cfg(layer)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (9,), 0, 64), np.int32
    )
    key = jax.random.PRNGKey(7)
    eng = ServingEngine(params, cfg, capacity=3, tokens_per_tick=2)
    res = eng.run([GenerationRequest(prompt_ids=prompt, max_new_tokens=7,
                                     temperature=0.9, key=key)])[0]
    assert res.finish_reason == "length"
    assert res.new_tokens.tolist() == solo(
        params, cfg, prompt, key, max_new_tokens=7, temperature=0.9
    )
    assert res.tokens.tolist() == prompt.tolist() + res.new_tokens.tolist()


def test_single_request_parity_with_eos(setup):
    """EOS finish: the engine stops where generate(eos_id=...) pins eos."""
    cfg, params = setup
    prompt = np.asarray([5, 9, 3, 1], np.int32)
    key = jax.random.PRNGKey(11)
    ref = solo(params, cfg, prompt, key, max_new_tokens=12)
    eos = ref[2]  # force a mid-stream finish on a token we know gets sampled
    ref_eos = solo(params, cfg, prompt, key, max_new_tokens=12, eos_id=eos)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=3)
    res = eng.run([GenerationRequest(prompt_ids=prompt, max_new_tokens=12,
                                     eos_id=eos, key=key)])[0]
    assert res.finish_reason == "eos"
    assert res.new_tokens[-1] == eos
    # the engine's stream is generate's, truncated at (and including) eos
    n = len(res.new_tokens)
    assert res.new_tokens.tolist() == ref_eos[:n]
    assert all(t == eos for t in ref_eos[n - 1:])


def test_interleaved_admit_evict_parity(setup):
    """Admit B mid-flight of A, finish A, admit C into A's freed slot —
    every request still matches its solo generate() run (satellite #3)."""
    cfg, params = setup
    keys = {n: jax.random.PRNGKey(20 + i) for i, n in enumerate("ABC")}
    prompts = {
        "A": np.asarray([1, 2, 3, 4, 5], np.int32),
        "B": np.asarray([7, 8, 9], np.int32),
        "C": np.asarray([4, 4, 4, 4, 4, 4, 4], np.int32),
    }
    budgets = {"A": 4, "B": 10, "C": 5}

    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=1)
    ids = {}
    ids["A"] = eng.submit(GenerationRequest(
        prompt_ids=prompts["A"], max_new_tokens=budgets["A"], key=keys["A"]))
    eng.step()  # A decoding alone
    eng.step()
    ids["B"] = eng.submit(GenerationRequest(
        prompt_ids=prompts["B"], max_new_tokens=budgets["B"], key=keys["B"]))
    eng.step()  # B admitted mid-flight of A
    ids["C"] = eng.submit(GenerationRequest(
        prompt_ids=prompts["C"], max_new_tokens=budgets["C"], key=keys["C"]))
    # capacity 2: C must wait in queue until A finishes and frees its slot
    assert eng.scheduler.depth == 1
    while eng.pending:
        eng.step()
    assert len(eng.results) == 3
    for name in "ABC":
        got = eng.results[ids[name]].new_tokens.tolist()
        want = solo(params, cfg, prompts[name], keys[name],
                    max_new_tokens=budgets[name])
        assert got == want, f"request {name} diverged: {got} vs {want}"


def test_top_k_one_slot_is_greedy(setup):
    """A top_k=1 slot decodes greedily whatever shares the pool."""
    cfg, params = setup
    prompt = np.asarray([3, 1, 4, 1, 5], np.int32)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2)
    res = eng.run([
        GenerationRequest(prompt_ids=prompt, max_new_tokens=6, top_k=1,
                          key=jax.random.PRNGKey(0)),
        GenerationRequest(prompt_ids=prompt[:3], max_new_tokens=6,
                          key=jax.random.PRNGKey(1)),
    ])
    want = solo(params, cfg, prompt, jax.random.PRNGKey(99),
                max_new_tokens=6, top_k=1)  # greedy: key-independent
    assert res[0].new_tokens.tolist() == want


def test_typed_prng_key_request_parity(setup):
    """A new-style jax.random.key request draws the same stream as the
    equivalent legacy PRNGKey (the pool stores raw key data)."""
    cfg, params = setup
    prompt = np.asarray([2, 4, 6], np.int32)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2)
    res = eng.run([
        GenerationRequest(prompt_ids=prompt, max_new_tokens=5,
                          key=jax.random.key(13)),
        GenerationRequest(prompt_ids=prompt, max_new_tokens=5,
                          key=jax.random.PRNGKey(13)),
    ])
    assert res[0].new_tokens.tolist() == res[1].new_tokens.tolist()
    assert res[0].new_tokens.tolist() == solo(
        params, cfg, prompt, jax.random.PRNGKey(13), max_new_tokens=5
    )


def test_failed_prefill_requeues_and_keeps_slot(setup, monkeypatch):
    """A prefill that raises must neither leak the slot nor drop the
    request: it returns to the queue head and a later step() serves it."""
    from mamba_distributed_tpu.serving import engine as engine_mod

    cfg, params = setup
    eng = ServingEngine(params, cfg, capacity=1, tokens_per_tick=2)
    rid = eng.submit(GenerationRequest(prompt_ids=np.asarray([1, 2], np.int32),
                                       max_new_tokens=4, key=jax.random.PRNGKey(0)))
    real_prefill = engine_mod._prefill
    monkeypatch.setattr(engine_mod, "_prefill",
                        lambda *a, **k: (_ for _ in ()).throw(RuntimeError("boom")))
    with pytest.raises(RuntimeError, match="boom"):
        eng.step()
    assert eng.pending == 1 and eng.scheduler.depth == 1  # not dropped
    assert eng._free == [0]  # slot not leaked
    monkeypatch.setattr(engine_mod, "_prefill", real_prefill)
    while eng.pending:
        eng.step()
    assert len(eng.results[rid].new_tokens) == 4  # served after recovery


def test_engine_rejects_oversized_top_k(setup):
    cfg, params = setup
    eng = ServingEngine(params, cfg, capacity=1, max_top_k=10)
    with pytest.raises(ValueError, match="max_top_k"):
        eng.submit(GenerationRequest(prompt_ids=np.ones(3, np.int32), top_k=11))


def test_streaming_serve_event_order(setup):
    """serve() streams TokenEvents: per-request indices are contiguous and
    the final event carries done + finish_reason."""
    cfg, params = setup
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2)
    reqs = [GenerationRequest(prompt_ids=np.asarray([2, 3], np.int32),
                              max_new_tokens=5, key=jax.random.PRNGKey(i))
            for i in range(2)]
    seen: dict[int, list] = {}
    for ev in eng.serve(reqs):
        seen.setdefault(ev.request_id, []).append(ev)
    for rid, evs in seen.items():
        assert [e.index for e in evs] == list(range(5))
        assert [e.done for e in evs] == [False] * 4 + [True]
        assert evs[-1].finish_reason == "length"
        assert [e.token for e in evs] == eng.results[rid].new_tokens.tolist()


# ------------------------------------------------------------ trace bounding


def test_generate_length_bucketing_traces():
    """Distinct prompt lengths inside one bucket share one jit trace
    (satellite #1: the retracing fix).  Uses its own model shape so the
    jit cache can't already hold these signatures from other tests."""
    from mamba_distributed_tpu.inference.generate import TRACE_COUNTS

    cfg = ModelConfig(d_model=16, n_layer=2, vocab_size=32, ssm_layer="mamba2",
                      headdim=4, chunk_size=8, d_state=8,
                      compute_dtype="float32")
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    key = jax.random.PRNGKey(0)
    before = TRACE_COUNTS["generate"]
    for t in (5, 6, 8):  # all in the 8-bucket
        generate(params, cfg, jnp.ones((1, t), jnp.int32), key,
                 max_new_tokens=4, top_k=16)
    assert TRACE_COUNTS["generate"] == before + 1
    generate(params, cfg, jnp.ones((1, 9), jnp.int32), key,
             max_new_tokens=4, top_k=16)
    assert TRACE_COUNTS["generate"] == before + 2  # 16-bucket: one more
    generate(params, cfg, jnp.ones((1, 13), jnp.int32), key,
             max_new_tokens=4, top_k=16)
    assert TRACE_COUNTS["generate"] == before + 2  # 13 reuses the 16-bucket


def test_engine_admission_does_not_retrace():
    """Prefill traces once per bucket; the decode tick traces once, no
    matter how many requests rotate through the slots.  Own model shape
    so the jit cache can't already hold these signatures."""
    from mamba_distributed_tpu.serving.engine import TRACE_COUNTS

    cfg = ModelConfig(d_model=16, n_layer=3, vocab_size=32, ssm_layer="mamba2",
                      headdim=4, chunk_size=8, d_state=8,
                      compute_dtype="float32")
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2, max_top_k=20)
    p0, t0 = TRACE_COUNTS["prefill"], TRACE_COUNTS["tick"]
    reqs = [GenerationRequest(prompt_ids=np.ones(n, np.int32), top_k=20,
                              max_new_tokens=3, key=jax.random.PRNGKey(n))
            for n in (5, 6, 7, 8, 3)]  # buckets: 8, 8, 8, 8, 8
    eng.run(reqs)
    assert TRACE_COUNTS["prefill"] == p0 + 1
    assert TRACE_COUNTS["tick"] == t0 + 1


def test_bucket_helper_contract():
    assert [next_pow2_bucket(t) for t in (1, 8, 9, 16, 17, 100)] == [
        8, 8, 16, 16, 32, 128
    ]
    with pytest.raises(ValueError):
        next_pow2_bucket(0)
    padded, mask = pad_to_bucket(jnp.asarray([[3, 4, 5]], jnp.int32), 8)
    assert padded.shape == (1, 8) and mask.shape == (1, 8)
    assert padded[0].tolist() == [0] * 5 + [3, 4, 5]
    assert mask[0].tolist() == [0.0] * 5 + [1.0, 1.0, 1.0]


# ----------------------------------------------------------------- metrics


def test_serving_metrics_counters(tmp_path):
    from mamba_distributed_tpu.utils.metrics import ServingMetrics

    jsonl = tmp_path / "serving.jsonl"
    m = ServingMetrics(capacity=4, jsonl_path=str(jsonl))
    m.record_prefill(prompt_tokens=16, dt_s=0.5)
    m.record_tick(occupied=2, queue_depth=3, tokens_emitted=2, dt_s=0.1)
    m.record_tick(occupied=4, queue_depth=0, tokens_emitted=4, dt_s=0.1)
    s = m.summary()
    assert s["ticks"] == 2 and s["decode_tokens"] == 6
    assert s["mean_slot_occupancy"] == 0.75  # (2+4)/(2*4)
    assert s["peak_queue_depth"] == 3 and s["mean_queue_depth"] == 1.5
    assert s["prefills"] == 1 and s["prefill_tokens"] == 16
    assert s["decode_tokens_per_sec"] == pytest.approx(30.0, rel=0.01)
    # both sides of the prefill rate were always tracked; summary now
    # exposes the ratio (satellite), plus the mean tick wall time
    assert s["prefill_tokens_per_sec"] == pytest.approx(32.0, rel=0.01)
    assert s["mean_tick_ms"] == pytest.approx(100.0, rel=0.01)
    import json

    lines = [json.loads(ln) for ln in jsonl.read_text().splitlines()]
    assert len(lines) == 2 and lines[0]["kind"] == "serving_tick"
    assert lines[1]["occupied"] == 4
    # a fresh metrics object truncates a reused path on first write
    # (two runs must never interleave); preserve_history() appends
    m2 = ServingMetrics(capacity=4, jsonl_path=str(jsonl))
    m2.record_tick(occupied=1, queue_depth=0, tokens_emitted=1, dt_s=0.1)
    assert len(jsonl.read_text().splitlines()) == 1
    m3 = ServingMetrics(capacity=4, jsonl_path=str(jsonl))
    m3.preserve_history()
    m3.record_tick(occupied=1, queue_depth=0, tokens_emitted=1, dt_s=0.1)
    assert len(jsonl.read_text().splitlines()) == 2


def test_engine_metrics_report_occupancy(setup):
    cfg, params = setup
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=4)
    eng.run([GenerationRequest(prompt_ids=np.ones(4, np.int32),
                               max_new_tokens=4, key=jax.random.PRNGKey(i))
             for i in range(3)])
    s = eng.metrics.summary()
    assert s["decode_tokens"] == 12 and s["ticks"] >= 2
    assert 0.0 < s["mean_slot_occupancy"] <= 1.0
    assert s["prefills"] == 3


# ------------------------------------------------- hybrid paged-KV serving


def hybrid_cfg(**kw):
    kw.setdefault("prefill_chunk_tokens", 16)
    kw.setdefault("prefill_tokens_per_tick", 16)
    return ModelConfig(d_model=32, n_layer=2, vocab_size=64,
                       ssm_layer="mamba2", headdim=8, chunk_size=16,
                       d_state=16, compute_dtype="float32",
                       attn_layer_idx=(1,), attn_num_heads=4,
                       attn_num_kv_heads=2, remat=False,
                       kv_page_tokens=8, kv_slot_tokens=64, **kw)


def rand_prompt(n, seed=1, vocab=64):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, vocab), np.int32
    )


def test_hybrid_engine_generate_parity():
    """THE acceptance scenario: a hybrid (mamba+attention) config is
    admitted by the slot pool, and every request's token stream is
    bit-identical to solo generate() — through admission mid-flight,
    a chunked-prefill long prompt, eviction, and slot+page reuse."""
    cfg = hybrid_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    keys = {n: jax.random.PRNGKey(40 + i) for i, n in enumerate("ALC")}
    prompts = {"A": rand_prompt(9, seed=2), "L": rand_prompt(53, seed=3),
               "C": rand_prompt(7, seed=4)}
    budgets = {"A": 4, "L": 5, "C": 6}

    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=1)
    ids = {}
    ids["A"] = eng.submit(GenerationRequest(
        prompt_ids=prompts["A"], max_new_tokens=budgets["A"], key=keys["A"]))
    eng.step()  # A decoding alone
    ids["L"] = eng.submit(GenerationRequest(
        prompt_ids=prompts["L"], max_new_tokens=budgets["L"], key=keys["L"]))
    eng.step()  # L admitted: chunks landing in its pool pages
    ids["C"] = eng.submit(GenerationRequest(
        prompt_ids=prompts["C"], max_new_tokens=budgets["C"], key=keys["C"]))
    while eng.pending:
        eng.step()
    for name in "ALC":
        got = eng.results[ids[name]].new_tokens.tolist()
        want = solo(params, cfg, prompts[name], keys[name],
                    max_new_tokens=budgets[name])
        assert got == want, f"hybrid request {name} diverged: {got} vs {want}"
    # the whole pool recycled: nothing leaked
    assert eng.page_pool.pages_in_use == 0


def test_hybrid_pages_freed_on_evict_no_alias():
    """Page-free-on-evict: an evicted request's pages return to the
    allocator; the slots that recycle them produce bit-exact streams
    (any stale-page aliasing would corrupt their attention reads), and
    live slots never share a physical page."""
    cfg = hybrid_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, capacity=1, tokens_per_tick=2)

    key_a = jax.random.PRNGKey(50)
    prompt_a = rand_prompt(40, seed=5)
    rid_a = eng.submit(GenerationRequest(prompt_ids=prompt_a,
                                         max_new_tokens=4, key=key_a))
    eng.step()
    tracked_a = next(iter(eng._slots.values()))
    pages_a = list(tracked_a.pages)
    assert len(pages_a) == -(-(40 + 4) // cfg.kv_page_tokens)
    while eng.pending:
        eng.step()
    # freed on evict: allocator got every page back, table row scrubbed
    assert eng.page_pool.pages_in_use == 0
    assert set(pages_a) <= set(eng.page_pool._free)
    assert (eng._page_tbl == 0).all() and (eng._kv_len == 0).all()

    # a new request recycles those pages and still matches generate()
    key_b = jax.random.PRNGKey(51)
    prompt_b = rand_prompt(33, seed=6)
    rid_b = eng.submit(GenerationRequest(prompt_ids=prompt_b,
                                         max_new_tokens=5, key=key_b))
    eng.step()
    tracked_b = next(iter(eng._slots.values()))
    assert set(tracked_b.pages) & set(pages_a)  # really recycled
    while eng.pending:
        eng.step()
    assert eng.results[rid_b].new_tokens.tolist() == solo(
        params, cfg, prompt_b, key_b, max_new_tokens=5
    )
    assert eng.results[rid_a].new_tokens.tolist() == solo(
        params, cfg, prompt_a, key_a, max_new_tokens=4
    )


def test_hybrid_live_slots_never_share_pages():
    """Allocator invariant under churn: across a mixed workload, the
    page sets of co-resident slots are always disjoint and within
    capacity."""
    cfg = hybrid_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, capacity=3, tokens_per_tick=2)
    for i in range(6):
        eng.submit(GenerationRequest(
            prompt_ids=rand_prompt(5 + 7 * i, seed=10 + i),
            max_new_tokens=3 + i, key=jax.random.PRNGKey(60 + i)))
    while eng.pending:
        eng.step()
        held = [t.pages for t in eng._slots.values() if t.pages]
        flat = [p for ps in held for p in ps]
        assert len(flat) == len(set(flat)), "live slots share a page"
        assert eng.page_pool.pages_in_use == len(flat)
    assert eng.page_pool.pages_in_use == 0


def test_hybrid_admission_waits_for_pages():
    """When the page pool can't cover a request it stays QUEUED (no
    mid-flight OOM is possible: pages are reserved up front) and is
    admitted once an eviction recycles pages."""
    import dataclasses

    cfg = hybrid_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    # pool of 8 pages: one 40+4-token request (6 pages) fills most of it
    cfg_small = dataclasses.replace(cfg, kv_pool_pages=8)
    eng = ServingEngine(params, cfg_small, capacity=2, tokens_per_tick=2)
    r1 = eng.submit(GenerationRequest(prompt_ids=rand_prompt(40, seed=7),
                                      max_new_tokens=4,
                                      key=jax.random.PRNGKey(70)))
    r2 = eng.submit(GenerationRequest(prompt_ids=rand_prompt(30, seed=8),
                                      max_new_tokens=4,
                                      key=jax.random.PRNGKey(71)))
    eng.step()
    # r2 needs 5 pages; only 2 are free while r1 holds 6 of 8
    assert eng.scheduler.depth == 1  # r2 still queued, slot free
    assert len(eng._free) == 1
    while eng.pending:
        eng.step()
    assert {r1, r2} <= set(eng.results)  # both served eventually
    # oversized requests are rejected up front, naming the knob
    with pytest.raises(ValueError, match="kv_slot_tokens"):
        eng.submit(GenerationRequest(prompt_ids=rand_prompt(61, seed=9),
                                     max_new_tokens=10))


def test_hybrid_tick_traces_once_across_occupancy():
    """The hybrid tick compiles once per page BUCKET, not per occupancy
    or length mix — requests coming and going reuse the trace."""
    from mamba_distributed_tpu.serving.engine import TRACE_COUNTS

    import dataclasses

    # own vocab size so the jit cache can't already hold the signature
    cfg = dataclasses.replace(hybrid_cfg(), vocab_size=48)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2,
                        max_top_k=20)
    t0 = TRACE_COUNTS["tick"]
    # all requests fit one page bucket (<= 2 pages of 8 tokens each)
    reqs = [GenerationRequest(prompt_ids=rand_prompt(n, seed=n), top_k=20,
                              max_new_tokens=12 - n,
                              key=jax.random.PRNGKey(n))
            for n in (3, 5, 4, 6)]
    eng.run(reqs)
    assert TRACE_COUNTS["tick"] == t0 + 1


def test_hybrid_request_larger_than_pool_rejected():
    """A request that could NEVER fit the (oversubscribed) page pool is
    rejected at submit instead of stalling the queue forever."""
    import dataclasses

    cfg = dataclasses.replace(hybrid_cfg(), kv_pool_pages=4)  # 32 tokens
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2)
    with pytest.raises(ValueError, match="page pool"):
        eng.submit(GenerationRequest(prompt_ids=rand_prompt(40, seed=1),
                                     max_new_tokens=4))
    # a pool-sized request still serves
    rid = eng.submit(GenerationRequest(prompt_ids=rand_prompt(20, seed=2),
                                       max_new_tokens=4,
                                       key=jax.random.PRNGKey(0)))
    while eng.pending:
        eng.step()
    assert len(eng.results[rid].new_tokens) == 4


def test_admission_deadlock_detected_at_admit_time():
    """The PR-5 deadlock fix: a reservation no amount of FUTURE
    evictions could ever satisfy must fail loudly at _admit instead of
    waiting forever behind other prefilling slots.  submit() already
    rejects such requests, so feed one past it (straight into the
    scheduler, as a custom front end might) while another slot is
    mid-flight — pre-fix, step() would requeue it silently every
    iteration with the queue stalled behind it."""
    import dataclasses

    cfg = dataclasses.replace(hybrid_cfg(), kv_pool_pages=4)  # 32 tokens
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2)
    ok = eng.submit(GenerationRequest(prompt_ids=rand_prompt(20, seed=2),
                                      max_new_tokens=4,
                                      key=jax.random.PRNGKey(0)))
    # 40 + 4 tokens => 6 pages > the whole 4-page pool
    doomed = eng.scheduler.submit(GenerationRequest(
        prompt_ids=rand_prompt(40, seed=1), max_new_tokens=4))
    with pytest.raises(RuntimeError, match="can never be admitted"):
        while eng.pending:
            eng.step()
    # the poison request was DROPPED (requeueing would park it at the
    # queue head and re-raise forever); the engine serves on untouched
    assert all(t.request_id != doomed.request_id
               for t in eng.scheduler._queue)
    while eng.pending:
        eng.step()
    assert len(eng.results[ok].new_tokens) == 4
