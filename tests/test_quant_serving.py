"""Quantized serving tests (ops/quant.py + the int8 KV page pools).

The contract under test, per ISSUE 11's acceptance criteria:

  * ROUND-TRIP — per-channel int8 quantization error is bounded by
    half a step per element, for every parameter class (column-scaled,
    row-scaled, embedding), with the scale axis matching the
    tensor-parallel axis so scales shard with their weights.
  * PARITY — quantized engine streams match solo ``generate()`` under
    ``assert_stream_close`` on every pinned config: mamba1/mamba2/
    hybrid, chunked longs, the (2, 2) TP mesh, a prefix-cache warm
    hit, and a disaggregated migration — because engine and generate
    run the IDENTICAL quantized math through the one shared decode
    cast.
  * KERNELS — the ragged paged decode/prefill kernels' fused dequant
    (and the prefill kernel's quantized page write) match the lax
    fallback at ragged rows, with the written int8 pages and scales
    agreeing between the two paths.
  * CAPACITY — int8 KV pools admit >= 1.9x the pages of bf16 at equal
    pool bytes (the ROADMAP capacity multiplier).
  * BYTE-STABILITY — with the default bf16 dtypes nothing changes:
    no quantized leaves, no new record fields, ``summary()["memory"]``
    stays None; and quant ON adds zero jit signatures across a
    repeated workload.

Runnable standalone: ``pytest -m quant``.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mamba_distributed_tpu.config import ModelConfig
from mamba_distributed_tpu.inference import generate
from mamba_distributed_tpu.inference.generate import _decode_params
from mamba_distributed_tpu.models import init_lm_params
from mamba_distributed_tpu.ops.quant import (
    assert_stream_close,
    dequantize,
    is_quantized,
    param_bytes,
)
from mamba_distributed_tpu.serving import GenerationRequest, ServingEngine

# fast is marked PER-TEST, and the heavier engine-level variants (TP
# mesh, router migration, pallas engine parity, per-layer weight-only
# parity, prefix warm hit, trace flatness) are -m slow per the tier-1
# wall-clock budget (the PR-8 precedent): tier-1 keeps the combined
# int8-weights+KV hybrid parity plus every cheap pin; `pytest -m
# quant` (or the slow tier) runs the whole surface
pytestmark = [pytest.mark.quant, pytest.mark.serving]

CHUNK = 16


def tiny_cfg(layer="mamba2", **kw):
    kw.setdefault("prefill_chunk_tokens", CHUNK)
    kw.setdefault("prefill_tokens_per_tick", CHUNK)
    kw.setdefault("vocab_size", 64)
    kw.setdefault("compute_dtype", "float32")
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


def solo(params, cfg, prompt, key, mesh=None, **kw):
    out = generate(params, cfg, jnp.asarray(prompt, jnp.int32)[None], key,
                   mesh=mesh, **kw)
    return np.asarray(out)[0, len(prompt):]


def mixed_requests(n_short=1, n_long=1, max_new=4):
    reqs = []
    for i in range(n_short):
        reqs.append(GenerationRequest(
            prompt_ids=rand_prompt(5 + 3 * i, seed=10 + i),
            max_new_tokens=max_new, key=jax.random.PRNGKey(100 + i)))
    for i in range(n_long):
        reqs.append(GenerationRequest(
            prompt_ids=rand_prompt(2 * CHUNK + 7 + i, seed=50 + i),
            max_new_tokens=max_new, key=jax.random.PRNGKey(200 + i)))
    return reqs


def assert_parity(params, cfg, requests, results, mesh=None):
    for r, res in zip(requests, results):
        want = solo(params, cfg, r.prompt_ids, r.key, mesh=mesh,
                    max_new_tokens=r.max_new_tokens)
        assert_stream_close(res.new_tokens, want)


# ------------------------------------------------------------- round trip


@pytest.mark.fast
def test_quantize_roundtrip_error_bounds():
    """|w - dequant(quant(w))| <= scale/2 per element, for every
    quantized parameter class — and the scale axis is the TP axis
    (column kernels: output axis; row kernels: input axis; embedding:
    vocab rows)."""
    cfg = tiny_cfg(serving_weight_dtype="int8", tie_embeddings=False)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    dp = _decode_params(params, cfg)

    def check(q, w, scale_bcast_shape):
        assert is_quantized(q)
        assert q["kernel"].dtype == jnp.int8
        assert q["scale"].shape == scale_bcast_shape
        err = np.abs(np.asarray(dequantize(q)) - np.asarray(w))
        bound = np.broadcast_to(np.asarray(q["scale"]) * 0.5 + 1e-7,
                                err.shape)
        assert (err <= bound).all()

    L = cfg.n_layer
    d_in_proj = params["blocks"]["mixer"]["in_proj"]["kernel"].shape[-1]
    # column-parallel: scale per output column (the "model" axis)
    check(dp["blocks"]["mixer"]["in_proj"],
          params["blocks"]["mixer"]["in_proj"]["kernel"],
          (L, 1, d_in_proj))
    # row-parallel: scale per input row
    check(dp["blocks"]["mixer"]["out_proj"],
          params["blocks"]["mixer"]["out_proj"]["kernel"],
          (L, cfg.d_inner, 1))
    # embedding + untied head: per vocab row / per vocab column
    V = cfg.vocab_size_padded
    check(dp["embedding"], params["embedding"], (V, 1))
    check(dp["lm_head"], params["lm_head"]["kernel"], (1, V))


@pytest.mark.fast
def test_decode_cast_quant_selectivity():
    """Conv, router, (mamba1) dt_proj and the SSM scalars never
    quantize; the default bf16 dtype leaves the whole tree unquantized
    (the byte-stable status quo)."""
    cfg = tiny_cfg("mamba1", serving_weight_dtype="int8")
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    dp = _decode_params(params, cfg)
    mixer = dp["blocks"]["mixer"]
    assert is_quantized(mixer["in_proj"]) and is_quantized(mixer["x_proj"])
    assert not is_quantized(mixer["conv"])
    assert not is_quantized(mixer["dt_proj"])
    assert mixer["dt_proj"]["kernel"].dtype == jnp.dtype(cfg.compute_dtype)
    assert mixer["A_log"].dtype == jnp.float32
    # default: nothing quantized anywhere
    dp0 = _decode_params(params, tiny_cfg("mamba1"))
    assert not any(is_quantized(x) for x in [
        dp0["embedding"], dp0["blocks"]["mixer"]["in_proj"]])
    # int8 weights really shrink the resident tree
    assert param_bytes(dp) < 0.5 * param_bytes(dp0)


@pytest.mark.fast
def test_config_rejects_bad_dtypes():
    with pytest.raises(ValueError, match="serving_weight_dtype"):
        ModelConfig(serving_weight_dtype="fp8")
    with pytest.raises(ValueError, match="kv_page_dtype"):
        ModelConfig(kv_page_dtype="int4")


# ----------------------------------------------------------- engine parity


@pytest.mark.slow
@pytest.mark.parametrize("layer", ["mamba2", "mamba1"])
def test_weight_quant_engine_generate_parity(layer):
    """Int8 weights: engine streams match solo generate() (short and
    chunked-long prompts) — both sides run the one shared quantized
    cast, so agreement is exact in practice and assert_stream_close
    pins it."""
    cfg = tiny_cfg(layer, serving_weight_dtype="int8")
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2)
    reqs = mixed_requests()
    assert_parity(params, cfg, reqs, eng.run(reqs))


def test_hybrid_int8_kv_engine_generate_parity():
    """Int8 KV pages + int8 weights on the hybrid stack: chunked-long
    and short prompts through slot/page churn all match generate()
    (the lax fallback path on CPU), and every page recycles."""
    cfg = hybrid_cfg(kv_page_dtype="int8", serving_weight_dtype="int8")
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=1)
    reqs = mixed_requests()
    assert_parity(params, cfg, reqs, eng.run(reqs))
    assert eng.page_pool.pages_in_use == 0


@pytest.mark.pallas
@pytest.mark.slow
def test_hybrid_int8_kv_parity_pallas_kernels(monkeypatch):
    """The same contract through the Pallas ragged kernels (interpret
    mode on CPU): in-kernel dequant + the prefill kernel's quantized
    fused page write."""
    monkeypatch.setenv("MDT_ATTN_IMPL", "pallas")
    cfg = hybrid_cfg(kv_page_dtype="int8")
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2)
    reqs = mixed_requests(n_short=1, n_long=1, max_new=4)
    assert_parity(params, cfg, reqs, eng.run(reqs))
    assert eng.page_pool.pages_in_use == 0


@pytest.mark.slow
def test_tp_mesh_int8_parity():
    """(data=2, model=2): int8 weights shard with their scales over the
    model axis (no cross-shard rescale) and streams still match
    generate(mesh=)."""
    cfg = hybrid_cfg(serving_data_shards=2, serving_model_shards=2,
                     serving_weight_dtype="int8", kv_page_dtype="int8")
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, capacity=4, tokens_per_tick=2)
    # scales carry the SAME partitioned axis as their kernels
    p = eng._params
    assert p["embedding"]["kernel"].sharding.spec[0] == "model"
    assert p["embedding"]["scale"].sharding.spec[0] == "model"
    assert p["blocks"]["mixer"]["in_proj"]["kernel"].sharding.spec[-1] == \
        "model"
    assert p["blocks"]["mixer"]["in_proj"]["scale"].sharding.spec[-1] == \
        "model"
    assert p["blocks"]["mixer"]["out_proj"]["scale"].sharding.spec[-2] == \
        "model"
    reqs = mixed_requests()
    assert_parity(params, cfg, reqs, eng.run(reqs), mesh=eng.mesh)


@pytest.mark.slow
def test_prefix_cache_warm_hit_int8_parity():
    """A warm full prefix-cache hit on an int8 engine (snapshot insert,
    zero prefill compute) still streams what generate() streams."""
    cfg = hybrid_cfg(kv_page_dtype="int8", serving_weight_dtype="int8",
                     prefix_cache_entries=32)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2)
    prompt = rand_prompt(2 * CHUNK, seed=3)
    key = jax.random.PRNGKey(9)
    eng.run([GenerationRequest(prompt_ids=prompt, max_new_tokens=4,
                               key=key)])  # populate
    res = eng.run([GenerationRequest(prompt_ids=prompt, max_new_tokens=4,
                                     key=key)])[0]  # warm full hit
    assert eng.metrics.prefix_full_hits >= 1
    assert_stream_close(res.new_tokens,
                        solo(params, cfg, prompt, key, max_new_tokens=4))
    # only the cache's pinned prefix pages remain resident (refcounted
    # holders — the int8 payloads AND their scales stay shareable)
    pinned = {p for e in eng.prefix_cache._entries.values()
              if e.kv_pages for p in e.kv_pages}
    assert eng.page_pool.pages_in_use == len(pinned)


@pytest.mark.disagg
@pytest.mark.slow
def test_migration_int8_parity():
    """A disaggregated prefill->decode migration ships int8 page
    payloads + their scales; the resumed stream matches generate()."""
    from mamba_distributed_tpu.serving import RequestRouter

    cfg = hybrid_cfg(kv_page_dtype="int8", serving_weight_dtype="int8",
                     disagg_prompt_threshold=CHUNK)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    router = RequestRouter(params, cfg, num_replicas=2, capacity=3,
                           tokens_per_tick=2, roles=["prefill", "decode"])
    reqs = mixed_requests(n_short=1, n_long=1)
    results = router.run(reqs)
    assert router.migrations == 1  # the long took the handoff
    assert_parity(params, cfg, reqs, results)


# ---------------------------------------------------------------- kernels


@pytest.mark.pallas
@pytest.mark.fast
def test_ragged_decode_kernel_vs_lax_int8():
    """In-kernel dequant matches the dequantizing-gather fallback at
    ragged rows (dead row, mid-page length, multi-page length)."""
    from mamba_distributed_tpu.models.attention import (
        _sdpa_positions,
        gather_kv_pages,
    )
    from mamba_distributed_tpu.ops.pallas.attention_kernels import (
        ragged_paged_decode_attention,
    )

    rng = np.random.default_rng(0)
    S, W, nkv, pg, hd, nh = 3, 4, 2, 8, 16, 4
    P = 1 + S * W
    kq = rng.integers(-127, 128, size=(P, nkv, pg, hd)).astype(np.int8)
    vq = rng.integers(-127, 128, size=(P, nkv, pg, hd)).astype(np.int8)
    ks = (rng.random((P, nkv)) * 0.05 + 0.001).astype(np.float32)
    vs = (rng.random((P, nkv)) * 0.05 + 0.001).astype(np.float32)
    tbl = np.arange(1, P).reshape(S, W).astype(np.int32)
    kv_len = np.asarray([0, 5, 29], np.int32)
    q = rng.standard_normal((S, nh, hd)).astype(np.float32)
    # the kernel reads layer 1 of a two-layer pool (layer 0: zeros)
    pool = lambda x: jnp.stack([jnp.zeros_like(x), jnp.asarray(x)])
    out = ragged_paged_decode_attention(
        jnp.asarray(q), pool(kq), pool(vq), 1,
        jnp.asarray(tbl), jnp.asarray(kv_len),
        k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs))
    kk, vv = gather_kv_pages(jnp.asarray(kq), jnp.asarray(vq),
                             jnp.asarray(tbl), k_scale=jnp.asarray(ks),
                             v_scale=jnp.asarray(vs), dtype=jnp.float32)
    qpos = np.maximum(kv_len - 1, 0)
    ref = _sdpa_positions(jnp.asarray(q)[:, None], kk, vv,
                          jnp.asarray(qpos)[:, None])[:, 0]
    live = kv_len > 0
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(ref)[live],
                               rtol=2e-5, atol=2e-5)


@pytest.mark.pallas
def test_ragged_prefill_kernel_vs_lax_int8(monkeypatch):
    """The prefill kernel's quantized fused write produces the SAME
    int8 pages and scales as the lax requant-merge, and the attend
    outputs agree — at ragged (lengths, pad) rows including a
    page-straddling resume."""
    from mamba_distributed_tpu.models.attention import (
        attention_mixer_chunk,
        init_attention_state,
    )

    cfg = hybrid_cfg(kv_page_dtype="int8")
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    ap = jax.tree.map(lambda x: x[0], params["attn_blocks"])["mixer"]
    b, c, W = 2, 16, 8
    # a one-layer pool: the mixers take the whole pool and a layer index
    kv0 = jax.tree.map(lambda x: x[None], init_attention_state(cfg, b, 64))
    tbl = 1 + np.arange(b * W, dtype=np.int32).reshape(b, W)
    lengths = np.asarray([5, 0], np.int32)  # mid-page resume + fresh row
    u = np.asarray(jax.random.normal(jax.random.PRNGKey(5), (b, c, 32)),
                   np.float32)
    mask = np.ones((b, c), np.float32)
    mask[1, :6] = 0.0  # left pad on the fresh row
    outs = {}
    for impl in ("xla", "pallas"):
        monkeypatch.setenv("MDT_ATTN_IMPL", impl)
        outs[impl] = attention_mixer_chunk(
            ap, cfg, jnp.asarray(u), kv0, 0, jnp.asarray(tbl),
            jnp.asarray(lengths), token_mask=jnp.asarray(mask))
    (y_x, kv_x), (y_p, kv_p) = outs["xla"], outs["pallas"]
    np.testing.assert_allclose(np.asarray(y_x), np.asarray(y_p),
                               rtol=3e-5, atol=3e-5)
    kxq, vxq, kxs, vxs = [np.asarray(x[0]) for x in kv_x]
    kpq, vpq, kps, vps = [np.asarray(x[0]) for x in kv_p]
    total = lengths + np.asarray([c, c - 6])
    for r in range(b):
        for j in range(W):
            if j * cfg.kv_page_tokens < total[r] and \
                    (j + 1) * cfg.kv_page_tokens > lengths[r]:
                p_ = tbl[r, j]
                np.testing.assert_array_equal(kxq[p_], kpq[p_])
                np.testing.assert_array_equal(vxq[p_], vpq[p_])
                np.testing.assert_allclose(kxs[p_], kps[p_], rtol=1e-6)
                np.testing.assert_allclose(vxs[p_], vps[p_], rtol=1e-6)


@pytest.mark.pallas
@pytest.mark.fast
def test_int8_kernels_tpu_lowering():
    """The REAL Pallas->Mosaic TPU lowering (no chip needed) of both
    int8 kernels: f32 scalar-prefetched scale arrays, int8 page blocks,
    and the prefill kernel's aliased int8 page outputs all lower — at a
    PRODUCTION-shaped pool (1025 pages x 8 kv heads: 32 KB per scale
    array, four of them prefetched by the prefill kernel), not just a
    toy size, because the scale arrays ride the SMEM scalar-prefetch
    channel and its capacity is the scaling ceiling (ROADMAP
    quantization residuals)."""

    from mamba_distributed_tpu.ops.pallas.attention_kernels import (
        ragged_paged_decode_attention,
        ragged_paged_prefill_attention,
    )

    S, nh, nkv, hd, pg, W = 64, 32, 8, 64, 64, 16
    P = 1 + S * W
    q = jnp.zeros((S, nh, hd), jnp.bfloat16)
    kp = jnp.zeros((2, P, nkv, pg, hd), jnp.int8)
    ks = jnp.ones((P, nkv), jnp.float32)  # one layer's scales
    tbl = jnp.zeros((S, W), jnp.int32)
    ln = jnp.zeros((S,), jnp.int32)

    def f(q, kp, vp, a, tbl, ln, ks, vs):
        return ragged_paged_decode_attention(
            q, kp, vp, a, tbl, ln, k_scale=ks, v_scale=vs, interpret=False)

    exp = jax.export.export(jax.jit(f), platforms=["tpu"])(
        q, kp, kp, jnp.int32(1), tbl, ln, ks, ks)
    assert exp.platforms == ("tpu",)

    b, c = 8, 256
    q2 = jnp.zeros((b, c, nh, hd), jnp.bfloat16)
    kc = jnp.zeros((b, c, nkv, hd), jnp.bfloat16)
    tbl2 = jnp.zeros((b, W), jnp.int32)
    ln2 = jnp.zeros((b,), jnp.int32)

    def g(q, kc, vc, kp, vp, a, tbl, ln, cr, kso, ksn, vso, vsn):
        return ragged_paged_prefill_attention(
            q, kc, vc, kp, vp, a, tbl, ln, cr,
            k_scale_old=kso, k_scale_new=ksn,
            v_scale_old=vso, v_scale_new=vsn, interpret=False)

    exp2 = jax.export.export(jax.jit(g), platforms=["tpu"])(
        q2, kc, kc, kp, kp, jnp.int32(1), tbl2, ln2, ln2, ks, ks, ks, ks)
    assert exp2.platforms == ("tpu",)


# --------------------------------------------------------------- capacity


@pytest.mark.fast
def test_int8_kv_capacity_ratio():
    """Int8 pools admit >= 1.9x the pages of bf16 at equal pool bytes
    (the acceptance floor the quant_kv_capacity bench row records)."""
    from mamba_distributed_tpu.serving import state_cache

    # realistic page granule (pg*hd >= 76 amortizes the 4-byte scale;
    # the hybrid-tiny bench point is 32x32 -> 1.98x)
    base = hybrid_cfg(compute_dtype="bfloat16", kv_page_tokens=32,
                      kv_slot_tokens=128)

    def bytes_per_page(c):
        pool = state_cache.init_pool(c, 4)
        leaves = jax.tree.leaves(pool["state"]["attn_blocks"])
        return sum(x.nbytes for x in leaves) / leaves[0].shape[1]

    bf16 = bytes_per_page(base)
    int8 = bytes_per_page(dataclasses.replace(base, kv_page_dtype="int8"))
    assert bf16 / int8 >= 1.9


# ----------------------------------------------- traces + byte stability


@pytest.mark.slow
def test_trace_counts_flat_with_quant_on():
    """Quant on adds no jit signatures across a repeated workload (the
    same flat-trace contract every serving feature keeps)."""
    from mamba_distributed_tpu.serving.engine import TRACE_COUNTS
    from mamba_distributed_tpu.serving.prefill import (
        TRACE_COUNTS as CHUNK_COUNTS,
    )

    cfg = hybrid_cfg(kv_page_dtype="int8", serving_weight_dtype="int8",
                     vocab_size=56)  # own signature space
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2)
    eng.run(mixed_requests(n_short=2, n_long=1, max_new=4))
    t0, c0 = TRACE_COUNTS["tick"], CHUNK_COUNTS["chunk"]
    eng.run(mixed_requests(n_short=2, n_long=1, max_new=4))
    assert TRACE_COUNTS["tick"] == t0
    assert CHUNK_COUNTS["chunk"] == c0


@pytest.mark.fast
def test_quant_off_byte_stable(tmp_path):
    """Default dtypes: no quantized leaves, no quant fields on tick
    records, summary()["memory"] is None — bf16 serving is the exact
    status quo."""
    from mamba_distributed_tpu.utils.metrics import ServingMetrics

    cfg = hybrid_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    path = str(tmp_path / "ticks.jsonl")
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2,
                        metrics=ServingMetrics(2, jsonl_path=path))
    eng.run(mixed_requests(n_short=2, n_long=0))
    assert not any(is_quantized(x) for x in [eng._params["embedding"]])
    ticks = [json.loads(l) for l in open(path)
             if json.loads(l)["kind"] == "serving_tick"]
    assert ticks and all(
        "quantized" not in t and "weight_bytes" not in t for t in ticks)
    assert eng.metrics.summary()["memory"] is None
    # pool stays the 2-tuple bf16-family layout
    assert len(eng.pool["state"]["attn_blocks"]) == 2


@pytest.mark.fast
def test_quant_tick_records_and_summary(tmp_path):
    """Int8 engines stamp quantized/weight_bytes/page_pool_bytes on
    every tick record and expose summary()["memory"]; obs_report
    renders the line."""
    import os
    import subprocess
    import sys

    from mamba_distributed_tpu.utils.metrics import ServingMetrics

    cfg = hybrid_cfg(kv_page_dtype="int8", serving_weight_dtype="int8")
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    path = str(tmp_path / "ticks.jsonl")
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2,
                        metrics=ServingMetrics(2, jsonl_path=path))
    eng.run(mixed_requests(n_short=2, n_long=0))
    ticks = [json.loads(l) for l in open(path)
             if json.loads(l)["kind"] == "serving_tick"]
    assert ticks
    for t in ticks:
        assert t["quantized"] == {"weights": "int8", "kv": "int8"}
        assert t["weight_bytes"] > 0 and t["page_pool_bytes"] > 0
    mem = eng.metrics.summary()["memory"]
    assert mem["weight_dtype"] == "int8" and mem["kv_dtype"] == "int8"
    assert mem["weight_bytes"] == ticks[-1]["weight_bytes"]
    assert mem["greedy_token_disagreements"] == 0
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "obs_report.py"),
         path, "--json"],
        capture_output=True, text=True, cwd=repo, timeout=120,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    report = json.loads(r.stdout)
    assert report["serving"]["memory"]["quantized"]["kv"] == "int8"


@pytest.mark.fast
def test_assert_stream_close_reports_disagreement():
    """The shared parity checker: exact agreement passes silently; a
    drifted stream raises, feeds the divergence sentinel's flight
    recorder, and bumps the metrics counter."""
    from mamba_distributed_tpu.obs.sentinel import DivergenceSentinel
    from mamba_distributed_tpu.utils.metrics import ServingMetrics

    assert assert_stream_close([1, 2, 3], [1, 2, 3]) == 0
    sent = DivergenceSentinel(dump_path=None)
    met = ServingMetrics(capacity=1)
    with pytest.raises(AssertionError, match="diverge at 2/4"):
        assert_stream_close([1, 2, 9, 9], [1, 2, 3, 4],
                            sentinel=sent, metrics=met, label="t")
    assert met.greedy_token_disagreements == 2
    events = sent.flight.events()
    assert events and events[-1]["kind"] == "quant_token_disagreement"
    assert events[-1]["first_divergence"] == 2
    # a loosened agreement floor tolerates the tail drift
    assert assert_stream_close([1, 2, 9, 9], [1, 2, 3, 4],
                               min_token_agreement=0.5) == 2
    # logit closeness is enforced over the matched prefix
    with pytest.raises(AssertionError, match="logits"):
        assert_stream_close([1, 2], [1, 2],
                            got_logits=np.zeros((2, 4)),
                            want_logits=np.ones((2, 4)))
