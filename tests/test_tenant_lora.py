"""Multi-tenant LoRA serving tests (serving/adapters.py + the segmented
batched-LoRA tick path).

The contract under test, per ISSUE 15's acceptance criteria:

  * REGISTRY — targets derive from the param tree (the linear()-routed
    projections _TP_RULES shards), factor shapes validate, the
    registered-adapter cap holds, and the npz file format round-trips.
  * CACHE — the AdapterCache generalizes the PagePool discipline:
    refcounts pin slots while streams use them, zero-ref residents
    evict LRU, double-release raises the NAMED AdapterCacheError, an
    unknown name the NAMED UnknownAdapterError, and an all-pinned
    cache makes admission WAIT (never a mid-flight miss).
  * PARITY — a heterogeneous-adapter batch's per-stream tokens match
    solo ``generate()`` on the MERGED weights ``W + (alpha/r)·A@B``
    via ``ops/quant.assert_stream_close`` (float re-association makes
    bit-exactness the wrong pin; greedy tokens agree exactly on this
    fp32 CPU matrix) — across mamba1/mamba2/hybrid, chunked longs,
    the (2, 2) TP mesh, prefix-warm hits, preempt/resume, tier
    migration, spec K>0 and tick compaction.
  * ISOLATION — prefix-cache keys carry the adapter identity (a warm
    hit under adapter X never seeds adapter Y), and id-0 rows are an
    exact no-op (a no-adapter stream on a LoRA engine is bit-identical
    to a LoRA-less engine's).
  * BYTE-STABILITY — ``lora_max_adapters=0`` (default) changes nothing:
    no record stamps, ``summary()["adapters"]`` None, and LoRA ON adds
    zero jit signatures across a repeated mixed-adapter workload (one
    compiled tick shape regardless of how many adapters are live).

Runnable standalone: ``pytest -m lora``.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mamba_distributed_tpu.config import ModelConfig
from mamba_distributed_tpu.inference import generate
from mamba_distributed_tpu.models import init_lm_params
from mamba_distributed_tpu.ops.quant import assert_stream_close
from mamba_distributed_tpu.serving import (
    AdapterCacheError,
    AdapterRegistry,
    GenerationRequest,
    RequestRouter,
    ServingEngine,
    UnknownAdapterError,
)
from mamba_distributed_tpu.serving.adapters import (
    AdapterCache,
    load_adapter_file,
    merge_adapter_params,
    save_adapter_file,
)

pytestmark = [pytest.mark.lora, pytest.mark.serving]

CHUNK = 16


def tiny_cfg(layer="mamba2", **kw):
    kw.setdefault("prefill_chunk_tokens", CHUNK)
    kw.setdefault("prefill_tokens_per_tick", CHUNK)
    kw.setdefault("vocab_size", 64)
    kw.setdefault("compute_dtype", "float32")
    kw.setdefault("lora_max_adapters", 4)
    kw.setdefault("lora_rank", 4)
    kw.setdefault("lora_alpha", 8.0)
    return ModelConfig(d_model=32, n_layer=2, ssm_layer=layer,
                       headdim=8, chunk_size=16, d_state=16, **kw)


def hybrid_cfg(**kw):
    kw.setdefault("kv_page_tokens", 8)
    kw.setdefault("kv_slot_tokens", 64)
    return tiny_cfg(attn_layer_idx=(1,), attn_num_heads=4,
                    attn_num_kv_heads=2, remat=False, **kw)


def rand_prompt(n, seed=1, vocab=64):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, vocab), np.int32
    )


def make_registry(cfg, params, names=("alice", "bob")):
    reg = AdapterRegistry(cfg, params)
    for i, name in enumerate(names):
        reg.register_random(name, seed=10 + i)
    return reg


def merged_solo(params, reg, name, cfg, prompt, key, mesh=None, max_new=4):
    """The parity reference: solo generate() on the merged weights."""
    merged = merge_adapter_params(params, reg, name)
    out = generate(merged, cfg, jnp.asarray(prompt, jnp.int32)[None], key,
                   max_new_tokens=max_new, top_k=1, mesh=mesh)
    return np.asarray(out)[0, len(prompt):]


def tenant_requests(max_new=4, adapters=("alice", "bob", None)):
    """One short + one chunked-long prompt per adapter, greedy."""
    reqs = []
    for i, name in enumerate(adapters):
        reqs.append(GenerationRequest(
            prompt_ids=rand_prompt(5 + 3 * i, seed=10 + i),
            max_new_tokens=max_new, top_k=1,
            key=jax.random.PRNGKey(100 + i), adapter=name))
        reqs.append(GenerationRequest(
            prompt_ids=rand_prompt(2 * CHUNK + 5 + i, seed=50 + i),
            max_new_tokens=max_new, top_k=1,
            key=jax.random.PRNGKey(200 + i), adapter=name))
    return reqs


def assert_parity(params, reg, cfg, requests, results, mesh=None):
    for r, res in zip(requests, results):
        want = merged_solo(params, reg, r.adapter, cfg, r.prompt_ids,
                           r.key, mesh=mesh, max_new=r.max_new_tokens)
        assert_stream_close(res.new_tokens, want,
                            label=f"adapter={r.adapter}")


# ------------------------------------------------------ registry basics


@pytest.mark.fast
def test_registry_targets_validation_and_merge():
    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    reg = AdapterRegistry(cfg, params)
    # targets = the linear()-routed stacked projections
    assert list(reg.targets) == ["blocks/mixer/in_proj",
                                 "blocks/mixer/out_proj"]
    n, d_in, d_out = reg.targets["blocks/mixer/in_proj"]
    assert (n, d_in) == (cfg.n_layer, cfg.d_model)
    # shape validation names the offender
    with pytest.raises(ValueError, match="A shape"):
        reg.register("bad", {"blocks/mixer/in_proj": {
            "A": np.zeros((n, d_in, 3)), "B": np.zeros((n, 3, d_out))}})
    with pytest.raises(ValueError, match="unknown target"):
        reg.register("bad", {"blocks/mixer/nope": {
            "A": np.zeros((1,)), "B": np.zeros((1,))}})
    # subset coverage is legal; uncovered targets contribute zero delta
    reg.register_random("inproj-only", seed=3,
                        targets=["blocks/mixer/in_proj"])
    merged = reg.merge(params, "inproj-only")
    assert not np.allclose(
        np.asarray(merged["blocks"]["mixer"]["in_proj"]["kernel"]),
        np.asarray(params["blocks"]["mixer"]["in_proj"]["kernel"]))
    np.testing.assert_array_equal(
        np.asarray(merged["blocks"]["mixer"]["out_proj"]["kernel"]),
        np.asarray(params["blocks"]["mixer"]["out_proj"]["kernel"]))
    # the registered cap is cfg.lora_max_adapters
    for i in range(cfg.lora_max_adapters - 1):
        reg.register_random(f"filler-{i}", seed=i)
    with pytest.raises(ValueError, match="registry full"):
        reg.register_random("one-too-many", seed=99)
    with pytest.raises(UnknownAdapterError):
        reg.factors("never-registered")


@pytest.mark.fast
def test_adapter_file_roundtrip(tmp_path):
    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    reg = AdapterRegistry(cfg, params)
    rng = np.random.default_rng(0)
    factors = {
        path: {"A": rng.normal(size=(n, d_in, cfg.lora_rank)),
               "B": rng.normal(size=(n, cfg.lora_rank, d_out))}
        for path, (n, d_in, d_out) in reg.targets.items()
    }
    path = str(tmp_path / "alice.npz")
    save_adapter_file(path, factors)
    loaded = load_adapter_file(path)
    assert set(loaded) == set(factors)
    for tpath in factors:
        np.testing.assert_allclose(loaded[tpath]["A"],
                                   factors[tpath]["A"].astype(np.float32))
    reg.register("alice", loaded)
    assert "alice" in reg


# ----------------------------------------------------- cache discipline


@pytest.mark.fast
def test_adapter_cache_refcount_lru_and_errors():
    cfg = dataclasses.replace(tiny_cfg(), lora_cache_slots=2)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    reg = make_registry(cfg, params, names=("a", "b", "c"))
    cache = AdapterCache(reg, cfg.effective_lora_cache_slots,
                         compute_dtype=cfg.compute_dtype)
    sa = cache.acquire("a")
    sb = cache.acquire("b")
    assert sa != sb and sa >= 1 and sb >= 1
    # both pinned: a third adapter must WAIT (None), never evict live
    assert cache.acquire("c") is None
    assert cache.misses == 2
    # release -> zero-ref resident, LRU-evictable; c now lands in a's slot
    cache.release("a")
    assert cache.resident("a")  # warm until evicted
    sc = cache.acquire("c")
    assert sc == sa
    assert cache.evictions == 1 and not cache.resident("a")
    # resident re-acquire is a hit, refcount 2
    assert cache.acquire("b") == sb
    assert cache.hits == 1 and cache.refcount("b") == 2
    cache.release("b")
    cache.release("b")
    with pytest.raises(AdapterCacheError, match="no holders"):
        cache.release("b")
    with pytest.raises(AdapterCacheError):
        cache.release("a")  # evicted: never silently
    with pytest.raises(UnknownAdapterError):
        cache.acquire("zelda")
    # row 0 of every pool is the reserved zero entry
    for pool in cache.pools.values():
        assert float(jnp.abs(pool["A"][:, 0]).max()) == 0.0
        assert float(jnp.abs(pool["B"][:, 0]).max()) == 0.0


def test_cache_full_admission_waits_then_serves():
    """capacity 2, ONE factor slot, two adapters: the second tenant's
    request waits for the first to finish (slot pinned), then admits —
    the page-pool wait contract, and both streams stay correct."""
    cfg = dataclasses.replace(tiny_cfg(), lora_cache_slots=1)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    reg = make_registry(cfg, params)
    eng = ServingEngine(params, cfg, capacity=2, max_top_k=1,
                        tokens_per_tick=2, adapters=reg)
    reqs = [GenerationRequest(prompt_ids=rand_prompt(6, seed=i),
                              max_new_tokens=4, top_k=1,
                              key=jax.random.PRNGKey(i), adapter=name)
            for i, name in enumerate(["alice", "bob"])]
    results = eng.run(reqs)
    assert_parity(params, reg, cfg, reqs, results)
    assert eng.adapter_cache.evictions == 1  # bob displaced idle alice


# ------------------------------------------------------- parity matrix


@pytest.mark.parametrize("layer", ["mamba2", "mamba1"])
def test_hetero_batch_parity(layer):
    """Heterogeneous adapters + a no-adapter stream co-batched (short
    and chunked-long prompts): per stream, tokens match solo generate()
    on the merged weights — zero greedy disagreements at fp32."""
    cfg = tiny_cfg(layer)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    reg = make_registry(cfg, params)
    eng = ServingEngine(params, cfg, capacity=6, max_top_k=1,
                        tokens_per_tick=2, adapters=reg)
    reqs = tenant_requests()
    assert_parity(params, reg, cfg, reqs, eng.run(reqs))


def test_hetero_batch_parity_hybrid():
    """Hybrid stacks: wqkv/out_proj factors ride the attention layers
    and the paged-KV chunk prefill binds the same adapter ids."""
    cfg = hybrid_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    reg = make_registry(cfg, params)
    assert "attn_blocks/mixer/wqkv" in reg.targets
    eng = ServingEngine(params, cfg, capacity=6, max_top_k=1,
                        tokens_per_tick=2, adapters=reg)
    reqs = tenant_requests()
    assert_parity(params, reg, cfg, reqs, eng.run(reqs))


def test_tp_mesh_lora_parity():
    """(data=2, model=2): A shards with a row-parallel base kernel's
    input axis, B with a column-parallel one's output axis — and
    heterogeneous streams still match merged-weights generate(mesh=)."""
    cfg = tiny_cfg(serving_data_shards=2, serving_model_shards=2)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    reg = make_registry(cfg, params)
    eng = ServingEngine(params, cfg, capacity=4, max_top_k=1,
                        tokens_per_tick=2, adapters=reg)
    p = eng._params
    in_lora = p["blocks"]["mixer"]["in_proj"]["lora"]
    out_lora = p["blocks"]["mixer"]["out_proj"]["lora"]
    # column-parallel in_proj: B shards d_out, A replicates
    assert in_lora["B"].sharding.spec[-1] == "model"
    assert all(s is None for s in in_lora["A"].sharding.spec)
    # row-parallel out_proj: A shards d_in, B replicates
    assert out_lora["A"].sharding.spec[-2] == "model"
    assert all(s is None for s in out_lora["B"].sharding.spec)
    reqs = tenant_requests(adapters=("alice", "bob"))
    assert_parity(params, reg, cfg, reqs, eng.run(reqs), mesh=eng.mesh)


def test_prefix_warm_keys_carry_adapter_identity():
    """The SAME prompt under adapter X (warm), then adapter Y, then X
    again: Y must NOT seed from X's snapshot (its stream matches
    merged-Y generate), and the X repeat is a genuine full hit."""
    cfg = dataclasses.replace(tiny_cfg(), prefix_cache_entries=32)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    reg = make_registry(cfg, params)
    eng = ServingEngine(params, cfg, capacity=2, max_top_k=1,
                        tokens_per_tick=2, adapters=reg)
    prompt = rand_prompt(2 * CHUNK + 5, seed=7)  # chunked layout

    def req(name, seed):
        return GenerationRequest(prompt_ids=prompt, max_new_tokens=4,
                                 top_k=1, key=jax.random.PRNGKey(seed),
                                 adapter=name)

    r1 = eng.run([req("alice", 1)])[0]
    assert_stream_close(r1.new_tokens, merged_solo(
        params, reg, "alice", cfg, prompt, jax.random.PRNGKey(1)))
    # adapter Y on the identical tokens: different identity, no reuse
    r2 = eng.run([req("bob", 1)])[0]
    assert_stream_close(r2.new_tokens, merged_solo(
        params, reg, "bob", cfg, prompt, jax.random.PRNGKey(1)))
    assert eng.metrics.prefix_full_hits == 0
    # the X repeat IS a full hit — warm stream identical to cold
    r3 = eng.run([req("alice", 1)])[0]
    assert eng.metrics.prefix_full_hits == 1
    assert r3.new_tokens.tolist() == r1.new_tokens.tolist()


def test_preempt_resume_parity():
    """A higher-priority arrival preempts a LoRA stream mid-decode; the
    resumed stream continues on its adapter exactly (the factor-slot
    ref rides the snapshot — no re-miss, no re-prefill)."""
    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    reg = make_registry(cfg, params)
    eng = ServingEngine(params, cfg, capacity=1, max_top_k=1,
                        tokens_per_tick=1, adapters=reg)
    low = GenerationRequest(prompt_ids=rand_prompt(6, seed=1),
                            max_new_tokens=8, top_k=1,
                            key=jax.random.PRNGKey(1), adapter="alice",
                            priority=0)
    high = GenerationRequest(prompt_ids=rand_prompt(5, seed=2),
                             max_new_tokens=3, top_k=1,
                             key=jax.random.PRNGKey(2), adapter="bob",
                             priority=5)
    eng.submit(low)
    for _ in range(3):
        eng.step()
    eng.submit(high)
    while eng.pending:
        eng.step()
    results = {r.request_id: r for r in eng.results.values()}
    assert eng.metrics.preemptions == 1
    assert_parity(params, reg, cfg, [low, high],
                  [results[low.request_id], results[high.request_id]])


def test_migration_carries_adapter():
    """Disaggregated tiers with a SHARED registry: a long LoRA prompt
    prefills on the prefill tier, migrates, and decodes on the decode
    tier under the same adapter — stream matches merged generate()."""
    cfg = tiny_cfg(disagg_prompt_threshold=CHUNK)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    reg = make_registry(cfg, params)
    router = RequestRouter(params, cfg, num_replicas=2, capacity=3,
                           roles=["prefill", "decode"],
                           tokens_per_tick=2, max_top_k=1, adapters=reg)
    reqs = [
        GenerationRequest(prompt_ids=rand_prompt(2 * CHUNK + 5, seed=1),
                          max_new_tokens=4, top_k=1,
                          key=jax.random.PRNGKey(1), adapter="alice"),
        GenerationRequest(prompt_ids=rand_prompt(6, seed=2),
                          max_new_tokens=4, top_k=1,
                          key=jax.random.PRNGKey(2), adapter="bob"),
    ]
    results = router.run(reqs)
    assert router.migrations == 1
    assert_parity(params, reg, cfg, reqs, results)
    # the artifact's request carried the adapter; the decode replica
    # re-pinned it from ITS OWN cache
    decode_eng = router.replicas[1].engine
    assert decode_eng.adapter_cache.resident("alice")


def test_placement_skips_adapterless_replicas():
    """Replicas with DIFFERENT registries (some workers preloaded the
    adapter, some didn't): placement skips a replica whose registry
    lacks the request's adapter and lands on one that has it — a
    servable request must never 404 on the cheapest replica's missing
    registration, and only an adapter NOBODY holds raises."""
    from mamba_distributed_tpu.serving.replica import EngineReplica

    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    reg_with = make_registry(cfg, params, names=("alice",))
    reg_without = AdapterRegistry(cfg, params)  # empty registry
    replicas = [
        EngineReplica(0, params, cfg, capacity=2, max_top_k=1,
                      retain_results=False, adapters=reg_without),
        EngineReplica(1, params, cfg, capacity=2, max_top_k=1,
                      retain_results=False, adapters=reg_with),
    ]
    router = RequestRouter(None, cfg, replicas=replicas)
    req = GenerationRequest(prompt_ids=rand_prompt(6, seed=3),
                            max_new_tokens=4, top_k=1,
                            key=jax.random.PRNGKey(3), adapter="alice")
    results = router.run([req])
    # replica 0 is cheaper (same load, lower id) but lacks the adapter:
    # the stream must have decoded on replica 1
    assert replicas[1].engine.metrics.finished_requests == 1
    assert replicas[0].engine.metrics.finished_requests == 0
    assert_parity(params, reg_with, cfg, [req], results)
    with pytest.raises(ValueError, match="zelda"):
        router.submit(GenerationRequest(prompt_ids=rand_prompt(4),
                                        top_k=1, adapter="zelda"))


def test_spec_decode_parity():
    """spec K=2 on a LoRA engine: the verify launch binds the same
    adapter ids, and the speculative stream matches merged-weights
    PLAIN greedy generate() (speculation is lossless)."""
    cfg = tiny_cfg(spec_tokens=2)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    reg = make_registry(cfg, params)
    eng = ServingEngine(params, cfg, capacity=3, max_top_k=1,
                        adapters=reg)
    reqs = [GenerationRequest(prompt_ids=rand_prompt(7 + i, seed=i),
                              max_new_tokens=6, top_k=1,
                              key=jax.random.PRNGKey(i), adapter=name)
            for i, name in enumerate(["alice", "bob", None])]
    results = eng.run(reqs)
    plain = dataclasses.replace(cfg, spec_tokens=0)
    for r, res in zip(reqs, results):
        want = merged_solo(params, reg, r.adapter, plain, r.prompt_ids,
                           r.key, max_new=r.max_new_tokens)
        assert_stream_close(res.new_tokens, want,
                            label=f"spec:{r.adapter}")


def test_tick_compaction_parity(monkeypatch):
    """Narrow ticks gather the adapter-id meta row with the rest of
    the axis-0 meta: low-occupancy heterogeneous streams match both
    the merged reference and a one-rung LoRA engine bit-exactly."""
    from mamba_distributed_tpu.serving import engine as engine_mod

    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    reg = make_registry(cfg, params)
    reqs = tenant_requests(adapters=("alice", "bob"))
    eng = ServingEngine(params, cfg, capacity=16, max_top_k=1,
                        tokens_per_tick=2, adapters=reg)
    assert eng._rungs == (8, 16)
    results = eng.run(reqs)
    assert eng.metrics.summary()["compaction"]["ticks_compacted"] > 0
    assert_parity(params, reg, cfg, reqs, results)
    monkeypatch.setattr(engine_mod, "RUNG_FLOOR_LANES", 16)
    off = ServingEngine(params, cfg, capacity=16, max_top_k=1,
                        tokens_per_tick=2, adapters=reg)
    assert off._rungs == (16,)
    for a, b in zip(results, off.run(tenant_requests(
            adapters=("alice", "bob")))):
        assert a.new_tokens.tolist() == b.new_tokens.tolist()


# ------------------------------------------------- isolation + stability


def test_no_adapter_rows_are_exact_noop():
    """A request WITHOUT an adapter on a LoRA engine is bit-identical
    to a LoRA-less engine's stream: row 0's zero factors add an exact
    +0.0 on the fp32 accumulator."""
    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    reg = make_registry(cfg, params)

    def req():
        return GenerationRequest(prompt_ids=rand_prompt(9, seed=4),
                                 max_new_tokens=6, top_k=1,
                                 key=jax.random.PRNGKey(4))

    on = ServingEngine(params, cfg, capacity=2, max_top_k=1,
                       adapters=reg).run([req()])[0]
    off_cfg = dataclasses.replace(cfg, lora_max_adapters=0)
    off = ServingEngine(params, off_cfg, capacity=2,
                        max_top_k=1).run([req()])[0]
    assert on.new_tokens.tolist() == off.new_tokens.tolist()


def test_lora_off_byte_stable(tmp_path):
    """The default (lora_max_adapters=0) engine stamps nothing: no
    adapter fields on tick/request records, summary()["adapters"] is
    None, and naming an adapter on a request is a loud ValueError."""
    from mamba_distributed_tpu.utils.metrics import ServingMetrics

    cfg = dataclasses.replace(tiny_cfg(), lora_max_adapters=0)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    jsonl = str(tmp_path / "ticks.jsonl")
    eng = ServingEngine(params, cfg, capacity=2, max_top_k=1,
                        metrics=ServingMetrics(2, jsonl_path=jsonl))
    eng.run([GenerationRequest(prompt_ids=rand_prompt(6), top_k=1,
                               max_new_tokens=3,
                               key=jax.random.PRNGKey(0))])
    assert eng.metrics.summary()["adapters"] is None
    with open(jsonl) as f:
        for line in f:
            rec = json.loads(line)
            assert not any(k.startswith("adapter") for k in rec)
    with pytest.raises(ValueError, match="lora_max_adapters=0"):
        eng.submit(GenerationRequest(prompt_ids=rand_prompt(4),
                                     top_k=1, adapter="alice"))


@pytest.mark.fast
def test_unknown_adapter_and_int8_rejection():
    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    reg = make_registry(cfg, params)
    eng = ServingEngine(params, cfg, capacity=2, adapters=reg)
    with pytest.raises(UnknownAdapterError, match="zelda"):
        eng.submit(GenerationRequest(prompt_ids=rand_prompt(4),
                                     adapter="zelda"))
    # UnknownAdapterError is a ValueError: the service wire marks it
    # retriable and the front end maps it to a 404 body
    assert issubclass(UnknownAdapterError, ValueError)
    with pytest.raises(ValueError, match="ROADMAP residual"):
        ServingEngine(params, dataclasses.replace(
            cfg, serving_weight_dtype="int8"), capacity=2, adapters=reg)


@pytest.mark.fast
def test_wire_request_adapter_roundtrip():
    """The adapter identity survives the service wire (added at
    WIRE_VERSION 3) — submits, failover replays, resume-token
    re-attaches and tier migrations all re-derive it from the request
    payload."""
    from mamba_distributed_tpu.serving.service import wire

    assert wire.WIRE_VERSION >= 3
    r = GenerationRequest(prompt_ids=np.arange(1, 6, dtype=np.int32),
                          adapter="alice", seed=7)
    r.prompt_ids = np.asarray(r.prompt_ids, np.int32)
    r2 = wire.decode_request(wire.encode_request(r))
    assert r2.adapter == "alice"
    r3 = wire.decode_request(wire.encode_request(GenerationRequest(
        prompt_ids=np.arange(1, 4, dtype=np.int32))))
    assert r3.adapter is None
    # a LoRA-less peer's frames (v2) fail with the NAMED version error
    with pytest.raises(wire.UnknownWireVersionError):
        wire.decode_msg(json.dumps(
            {"v": 2, "type": "submit", "payload": {}}).encode())


def test_flat_trace_counts_and_telemetry(tmp_path):
    """One compiled tick shape regardless of how many distinct adapters
    are live: a second mixed-adapter wave adds ZERO jit traces.  Tick
    records carry the adapter gauges and request records the adapter
    name; obs_report renders the adapters: line."""
    from mamba_distributed_tpu.serving import engine as engine_mod
    from mamba_distributed_tpu.serving import prefill as prefill_mod
    from mamba_distributed_tpu.utils.metrics import ServingMetrics

    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    reg = make_registry(cfg, params, names=("alice", "bob", "carol"))
    jsonl = str(tmp_path / "ticks.jsonl")
    eng = ServingEngine(params, cfg, capacity=6, max_top_k=1,
                        tokens_per_tick=2, adapters=reg,
                        metrics=ServingMetrics(6, jsonl_path=jsonl))
    eng.run(tenant_requests(adapters=("alice", "bob", None)))
    counts0 = (dict(engine_mod.TRACE_COUNTS),
               dict(prefill_mod.TRACE_COUNTS))
    # a NEW adapter mix (carol live, alice evictable) — same shapes
    eng.run(tenant_requests(adapters=("carol", "bob", None)))
    assert (dict(engine_mod.TRACE_COUNTS),
            dict(prefill_mod.TRACE_COUNTS)) == counts0
    summary = eng.metrics.summary()["adapters"]
    assert summary["resident"] == 3
    assert summary["cache_misses"] == 3  # one upload per adapter
    assert summary["peak_live"] >= 2
    ticks = reqs = 0
    with open(jsonl) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("kind") == "serving_tick":
                assert "adapters_resident" in rec
                assert "adapters_live" in rec
                ticks += 1
            elif rec.get("kind") == "request":
                if rec.get("adapter"):
                    reqs += 1
    assert ticks and reqs >= 4
    # obs_report renders the adapters: line from the record stream
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "scripts/obs_report.py", jsonl],
        capture_output=True, text=True, check=True,
    ).stdout
    assert "adapters:" in out


def test_http_unknown_adapter_404():
    """POST /v1/generate with an adapter nobody holds answers 404 with
    the NAMED UnknownAdapterError body — never a hang, never a silent
    base-model stream (in-process replicas; no subprocesses)."""
    import http.client

    from mamba_distributed_tpu.serving.replica import EngineReplica
    from mamba_distributed_tpu.serving.service.server import (
        FabricController,
        FabricHTTPServer,
    )

    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    reg = make_registry(cfg, params)
    replicas = [EngineReplica(0, params, cfg, capacity=2, max_top_k=1,
                              retain_results=False, adapters=reg)]
    router = RequestRouter(None, cfg, replicas=replicas,
                           retain_results=False)
    controller = FabricController(router)
    controller.start()
    http_srv = FabricHTTPServer(controller)
    port = http_srv.start_background()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        body = json.dumps({"prompt_ids": [1, 2, 3], "max_new_tokens": 2,
                           "top_k": 1, "adapter": "zelda"})
        conn.request("POST", "/v1/generate", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        payload = json.loads(resp.read())
        assert resp.status == 404
        assert payload["error_type"] == "UnknownAdapterError"
        conn.close()
        # a KNOWN adapter streams fine through the same fabric
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        body = json.dumps({"prompt_ids": [1, 2, 3], "max_new_tokens": 2,
                           "top_k": 1, "seed": 3, "adapter": "alice"})
        conn.request("POST", "/v1/generate", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        events = [json.loads(line[6:])
                  for line in resp.read().decode().splitlines()
                  if line.startswith("data: ")]
        assert events and events[-1]["done"]
        toks = [e["token"] for e in events]
        want = merged_solo(params, reg, "alice", cfg,
                           np.asarray([1, 2, 3], np.int32),
                           jax.random.PRNGKey(3), max_new=2)
        # seed-keyed request: PRNGKey(seed) is the solo reference key
        assert_stream_close(toks, want, label="http")
        conn.close()
    finally:
        http_srv.stop()
        controller.stop()
        controller.join(timeout=10)
