"""Ragged paged attention tests: the Pallas decode + prefill kernels vs
the lax gather fallback (interpret mode on CPU; the same kernels compile
for real on TPU via jax.export), trace pinning across occupancies, and
the head-major paged-cache helpers in models/attention.py.

Everything here carries the ``pallas`` marker (pytest -m pallas) so the
kernel surface — parity, ragged skips, lowering pins — can be
re-verified in isolation after kernel work.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mamba_distributed_tpu.models.attention import (
    _sdpa_positions,
    gather_kv_pages,
)
from mamba_distributed_tpu.ops.pallas.attention_kernels import (
    TRACE_COUNTS,
    _pick_page_block,
    _window_pages,
    ragged_paged_decode_attention,
    ragged_paged_prefill_attention,
)

pytestmark = pytest.mark.pallas

# The kernels take the WHOLE (A, P, nkv, pg, hd) pool and a layer index.
# The cases below are built on one layer's pages: ``decode_at`` and
# ``prefill_at`` plant them as layer LAYER of a three-layer pool whose
# other layers are poison, so a read of the wrong layer shows in every
# case, and hand the indexed layer's pages back.
LAYER, LAYERS = 1, 3


def pool_of(pages, layer=LAYER):
    poison = 77 if pages.dtype == jnp.int8 else 3e4
    pool = jnp.full((LAYERS,) + pages.shape, poison, pages.dtype)
    return pool.at[layer].set(pages)


def decode_at(q, kp, vp, tbl, kv_len, layer=LAYER, **kw):
    return ragged_paged_decode_attention(
        q, pool_of(kp, layer), pool_of(vp, layer), layer, tbl, kv_len, **kw
    )


def prefill_at(q, kc, vc, kp, vp, tbl, lens, creal, layer=LAYER, **kw):
    o, kpool, vpool = ragged_paged_prefill_attention(
        q, kc, vc, pool_of(kp, layer), pool_of(vp, layer), layer, tbl, lens,
        creal, **kw
    )
    # the write is the indexed layer's alone (trash page excepted)
    for pool, pages in ((kpool, kp), (vpool, vp)):
        for a in set(range(LAYERS)) - {layer}:
            np.testing.assert_array_equal(
                np.asarray(pool[a, 1:]), np.asarray(pool_of(pages)[a, 1:])
            )
    return o, kpool[layer], vpool[layer]


def paged_case(rng, S=4, nh=8, nkv=2, hd=32, pg=8, W=4, P=17,
               dtype=jnp.float32, seed_lens=None):
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (S, nh, hd), dtype)
    # HEAD-MAJOR pool: (P, nkv, pg, hd)
    k_pages = jax.random.normal(ks[1], (P, nkv, pg, hd), dtype)
    v_pages = jax.random.normal(ks[2], (P, nkv, pg, hd), dtype)
    # disjoint per-row pages (pool-allocator invariant), page 0 = trash
    perm = 1 + np.random.default_rng(0).permutation(P - 1)[: S * W]
    tbl = jnp.asarray(perm.reshape(S, W), jnp.int32)
    lens = seed_lens if seed_lens is not None else [5, 0, W * pg, 17]
    lens = (lens * (1 + S // len(lens)))[:S]
    kv_len = jnp.asarray(jnp.minimum(jnp.asarray(lens), W * pg), jnp.int32)
    return q, k_pages, v_pages, tbl, kv_len


def lax_ref(q, k_pages, v_pages, tbl, kv_len):
    kk, vv = gather_kv_pages(k_pages, v_pages, tbl)
    return _sdpa_positions(q[:, None], kk, vv, (kv_len - 1)[:, None])[:, 0]


@pytest.mark.parametrize("shapes", [
    dict(),                                   # GQA rep=4
    dict(nh=4, nkv=4),                        # MHA rep=1
    dict(nh=8, nkv=1, hd=64),                 # MQA rep=8
    dict(S=6, W=2, pg=16, P=24),              # fewer, bigger pages
])
def test_ragged_kernel_matches_lax(rng, shapes):
    q, kp, vp, tbl, kv_len = paged_case(rng, **shapes)
    got = decode_at(q, kp, vp, tbl, kv_len, interpret=True)
    ref = lax_ref(q, kp, vp, tbl, kv_len)
    live = np.asarray(kv_len) > 0
    np.testing.assert_allclose(
        np.asarray(got)[live], np.asarray(ref)[live], atol=1e-5, rtol=1e-5
    )
    # rows with nothing cached (empty slots) emit zeros, never NaN
    assert not np.isnan(np.asarray(got)).any()
    assert (np.asarray(got)[~live] == 0).all()


def test_ragged_kernel_ignores_pages_past_length(rng):
    """Poisoning every page BEYOND a row's kv_len must not change its
    output — the ragged skip really skips (also proves a recycled page
    can't leak into a slot whose table no longer names it)."""
    q, kp, vp, tbl, kv_len = paged_case(rng, seed_lens=[5, 9, 12, 3])
    base = decode_at(q, kp, vp, tbl, kv_len, interpret=True)
    pg = kp.shape[2]
    npg = np.array(kp)
    nvg = np.array(vp)
    for s, ln in enumerate(np.asarray(kv_len)):
        for j in range(tbl.shape[1]):
            if j * pg >= ln:
                npg[np.asarray(tbl)[s, j]] = 1e9
                nvg[np.asarray(tbl)[s, j]] = -1e9
    # in-page positions past kv_len inside the LAST live page too
    poisoned = decode_at(
        q, jnp.asarray(npg), jnp.asarray(nvg), tbl, kv_len, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(base), np.asarray(poisoned))


def test_lax_gather_live_extent_masks_dead_pages(rng):
    """``gather_kv_pages(live_pages=)`` — the lax fallback's answer to
    the kernels' ragged page skip: table entries at or past each row's
    live extent redirect to the trash page, so the gather's read
    traffic scales with LIVE tokens (CPU-serving deployments stop
    paying O(pool) per tick), and poisoned dead pages can't change any
    output (their positions are hard-masked to -inf downstream)."""
    q, kp, vp, tbl, kv_len = paged_case(rng, seed_lens=[5, 9, 12, 3])
    pg = kp.shape[2]
    live = (np.asarray(kv_len) + pg - 1) // pg
    live = np.maximum(live, 1).astype(np.int32)
    kk, _ = gather_kv_pages(kp, vp, tbl, jnp.asarray(live))
    # unit check: the gathered view holds the trash page past each
    # row's live extent, the real pages inside it
    for s in range(tbl.shape[0]):
        for j in range(tbl.shape[1]):
            want = kp[tbl[s, j]] if j < live[s] else kp[0]
            np.testing.assert_array_equal(
                np.asarray(kk)[s, j * pg:(j + 1) * pg],
                np.moveaxis(np.asarray(want), 1, 0),
            )
    # end-to-end check: poison every dead page — the masked SDPA over
    # the live-extent gather is bit-identical to the clean full gather
    ref = _sdpa_positions(
        q[:, None], *gather_kv_pages(kp, vp, tbl), (kv_len - 1)[:, None]
    )
    npg, nvg = np.array(kp), np.array(vp)
    for s, ln in enumerate(np.asarray(kv_len)):
        for j in range(tbl.shape[1]):
            if j >= live[s]:
                npg[np.asarray(tbl)[s, j]] = 1e9
                nvg[np.asarray(tbl)[s, j]] = -1e9
    got = _sdpa_positions(
        q[:, None],
        *gather_kv_pages(jnp.asarray(npg), jnp.asarray(nvg), tbl,
                         jnp.asarray(live)),
        (kv_len - 1)[:, None],
    )
    rows_live = np.asarray(kv_len) > 0
    np.testing.assert_array_equal(np.asarray(got)[rows_live],
                                  np.asarray(ref)[rows_live])


def test_ragged_kernel_one_trace_across_occupancies(rng):
    """One jit trace covers every occupancy / length mix at a fixed
    (S, W) layout — the serving tick's no-retrace contract."""
    q, kp, vp, tbl, _ = paged_case(rng)

    fn = jax.jit(
        lambda q, kp, vp, tbl, ln: decode_at(
            q, kp, vp, tbl, ln, interpret=True
        )
    )
    before = TRACE_COUNTS["ragged_decode"]
    for lens in ([1, 1, 1, 1], [0, 0, 0, 5], [32, 17, 0, 8], [3, 32, 9, 1]):
        fn(q, kp, vp, tbl, jnp.asarray(lens, jnp.int32)).block_until_ready()
    assert TRACE_COUNTS["ragged_decode"] == before + 1


def test_ragged_kernel_tpu_lowering(rng):
    """The REAL Pallas->Mosaic lowering path (no chip needed), including
    the scalar-prefetched page-table index map."""
    S, nh, nkv, hd, pg, W, P = 8, 8, 2, 64, 16, 4, 33
    q = jnp.zeros((S, nh, hd), jnp.bfloat16)
    kp = jnp.zeros((LAYERS, P, nkv, pg, hd), jnp.bfloat16)
    tbl = jnp.zeros((S, W), jnp.int32)
    ln = jnp.zeros((S,), jnp.int32)

    # the layer index traced, as the group scan hands it over
    def f(q, kp, vp, a, tbl, ln):
        return ragged_paged_decode_attention(q, kp, vp, a, tbl, ln,
                                             interpret=False)

    exp = jax.export.export(jax.jit(f), platforms=["tpu"])(
        q, kp, kp, jnp.int32(LAYER), tbl, ln)
    assert exp.platforms == ("tpu",)


# the two longdoc cells' attention shapes: (query heads, head width,
# attention layers in the pool), 4 KV heads and 64-token pages on both
# (benchmark/configs/hybrid-280m.json, falcon-h1-34b.json)
CELL_HEADS = {"hybrid-280m": (12, 64, 8), "falcon-h1-34b": (20, 128, 6)}


@pytest.mark.parametrize("W", [20, 4], ids=["W20_not_a_multiple_of_B",
                                            "W4_narrower_than_a_block"])
@pytest.mark.parametrize("pool_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("cell", sorted(CELL_HEADS))
def test_decode_block_walk_edges_match_lax(rng, cell, pool_dtype, W):
    """The block walk at the cells' head shapes against the lax path, a lane
    at every edge of it: nothing cached, one token, one page, one token
    short of a block, a block, one token into the next, the whole table;
    under a table that B does not divide and under one narrower than B
    (which is then one block).  Every table entry past a lane's live pages
    names a page of poison."""
    nh, hd, _ = CELL_HEADS[cell]
    nkv, pg = 4, 64
    quant = pool_dtype == "int8"
    B = _pick_page_block(W, nkv, pg, hd, 1 if quant else 2)
    assert B == min(8, W)
    lens = sorted({0, 1, pg, B * pg - 1, B * pg, min(B * pg + 1, W * pg),
                   W * pg})
    S = len(lens)
    P = 2 + S * W
    ks = jax.random.split(rng, 5)
    q = jax.random.normal(ks[0], (S, nh, hd), jnp.float32)
    if quant:
        kp = jax.random.randint(ks[1], (P, nkv, pg, hd), -127, 128).astype(
            jnp.int8)
        vp = jax.random.randint(ks[2], (P, nkv, pg, hd), -127, 128).astype(
            jnp.int8)
        scale = lambda k: 0.001 + 0.05 * jax.random.uniform(
            k, (P, nkv), jnp.float32)
        kw = dict(k_scale=scale(ks[3]), v_scale=scale(ks[4]))
        tol = dict(atol=3e-5, rtol=3e-5)
    else:
        q = q.astype(jnp.bfloat16)
        kp = jax.random.normal(ks[1], (P, nkv, pg, hd), jnp.bfloat16)
        vp = jax.random.normal(ks[2], (P, nkv, pg, hd), jnp.bfloat16)
        kw = {}
        # the kernel rounds the probabilities to bfloat16 for the value
        # product; the lax path below is float32 throughout
        tol = dict(atol=4e-3, rtol=2e-2)
    # page P-1 is poison (finite: the pool's contract, as a recycled page's
    # residue is); a lane's dead table entries name it
    kp = kp.at[P - 1].set(100 if quant else 3e4)
    vp = vp.at[P - 1].set(-100 if quant else -3e4)
    tbl = 1 + np.arange(S * W, dtype=np.int32).reshape(S, W)
    for s, ln in enumerate(lens):
        tbl[s, -(-ln // pg):] = P - 1
    tbl, kv_len = jnp.asarray(tbl), jnp.asarray(lens, jnp.int32)
    got = decode_at(q, kp, vp, tbl, kv_len, interpret=True, **kw)
    kk, vv = gather_kv_pages(kp, vp, tbl, dtype=jnp.float32, **kw)
    ref = _sdpa_positions(
        q.astype(jnp.float32)[:, None], kk.astype(jnp.float32),
        vv.astype(jnp.float32), jnp.maximum(kv_len - 1, 0)[:, None])[:, 0]
    got = np.asarray(got, np.float32)
    assert not np.isnan(got).any()
    assert (got[0] == 0).all()                   # the lane with kv_len 0
    np.testing.assert_allclose(got[1:], np.asarray(ref)[1:], **tol)


@pytest.mark.parametrize("W, B", [(20, 8), (16, 8), (4, 4), (5, 2)])
def test_window_pages_live_then_still(W, B):
    """What the decode kernel's index maps read: window i of block j shows
    page j*B + i of the lane while that page is live; a dead window shows
    what it showed a block earlier (so the pipeline fetches nothing for
    it), back to its last live block or, if it never had one, to block 0."""
    pg = 8
    lens = np.asarray([0, 1, pg, B * pg - 1, B * pg, B * pg + 1,
                       W * pg - 1, W * pg], np.int32)
    S = len(lens)
    tbl = 1 + np.arange(S * W, dtype=np.int32).reshape(S, W)
    got = np.asarray(_window_pages(jnp.asarray(tbl), jnp.asarray(lens), pg, B))
    nb = -(-W // B)
    assert got.shape == (S, nb * B)
    live = -(-lens // pg)
    for s in range(S):
        for j in range(nb):
            for i in range(B):
                page = j * B + i
                if page < live[s]:
                    assert got[s, page] == tbl[s, page]
                elif j > 0:
                    assert got[s, page] == got[s, page - B]
                else:
                    assert got[s, page] == tbl[s, min(i, W - 1)]


@pytest.mark.parametrize("shape, want", [
    ((128, 4, 64, 128, 2), 8),     # falcon-h1-34b's tick: 512 tokens
    ((128, 4, 64, 64, 2), 8),      # hybrid-280m's: a 64-wide row pads to 128
    ((4, 4, 64, 128, 2), 4),       # a table narrower than a block: one block
    ((128, 8, 64, 64, 1), 8),      # int8 pages
    ((128, 4, 16, 128, 2), 32),    # small pages: still 512 tokens
    ((128, 32, 256, 256, 2), 1),   # a 4 MB page: the VMEM budget, at least 1
])
def test_pick_page_block_from_shapes(shape, want):
    assert _pick_page_block(*shape) == want


@pytest.mark.parametrize("S", [8, 16])
@pytest.mark.parametrize("cell", sorted(CELL_HEADS))
def test_ragged_kernel_tpu_lowering_at_the_cells_shapes(cell, S):
    """The Mosaic lowering (``jax.export``, no chip) of the decode kernel as
    the two longdoc cells' ticks call it: the whole pool of 16 slots x 128
    pages, 8,192-token tables, both rungs of the ladder, the layer traced."""
    nh, hd, A = CELL_HEADS[cell]
    nkv, pg, W, P = 4, 64, 128, 2049
    sds = jax.ShapeDtypeStruct
    pool = sds((A, P, nkv, pg, hd), jnp.bfloat16)

    def f(q, kp, vp, a, tbl, ln):
        return ragged_paged_decode_attention(q, kp, vp, a, tbl, ln,
                                             interpret=False)

    exp = jax.export.export(jax.jit(f), platforms=["tpu"])(
        sds((S, nh, hd), jnp.bfloat16), pool, pool, sds((), jnp.int32),
        sds((S, W), jnp.int32), sds((S,), jnp.int32))
    assert exp.platforms == ("tpu",)
    assert exp.out_avals[0].shape == (S, nh, hd)


def test_attention_step_kernel_path_matches_lax(rng, monkeypatch):
    """attn_impl='pallas' routes the decode step through the ragged
    kernel and reproduces the lax gather path."""
    from mamba_distributed_tpu.config import ModelConfig
    from mamba_distributed_tpu.models.attention import (
        attention_mixer_step,
        init_attention_params,
        init_attention_state,
        attention_page_meta,
    )

    kw = dict(d_model=64, n_layer=2, vocab_size=64, ssm_layer="mamba2",
              headdim=32, d_state=32, chunk_size=16,
              compute_dtype="float32", attn_layer_idx=(1,),
              attn_num_heads=4, attn_num_kv_heads=2, remat=False,
              kv_page_tokens=8, kv_slot_tokens=64)
    cfg_x = ModelConfig(**kw)
    cfg_p = ModelConfig(**kw, attn_impl="pallas")
    params = init_attention_params(rng, cfg_x)
    b = 3
    kv = jax.tree.map(pool_of, init_attention_state(cfg_x, b, 32))
    tbl, _ = attention_page_meta(cfg_x, b, 32)
    lengths = jnp.asarray([0, 5, 12], jnp.int32)
    u = jax.random.normal(jax.random.fold_in(rng, 1), (b, 64), jnp.float32)
    # seed the caches identically through a few lax steps first
    for i in range(3):
        y_x, kv = attention_mixer_step(params, cfg_x, u + i, kv, LAYER, tbl,
                                       lengths + i)
    y_ref, kv_ref = attention_mixer_step(params, cfg_x, u, kv, LAYER, tbl,
                                         lengths + 3)
    y_pal, kv_pal = attention_mixer_step(params, cfg_p, u, kv, LAYER, tbl,
                                         lengths + 3)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-5)
    for a, c in zip(jax.tree.leaves(kv_pal), jax.tree.leaves(kv_ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


# ------------------------------------------------ ragged paged PREFILL kernel


def prefill_case(rng, b=3, c=16, nh=8, nkv=2, hd=32, pg=8, W=8, P=29,
                 lens=(0, 5, 17), reals=(16, 11, 16), dtype=jnp.float32):
    """One chunk step's inputs: RoPE'd chunk q/k/v, a seeded head-major
    pool, disjoint tables, per-row (lengths, chunk_real)."""
    ks = jax.random.split(rng, 5)
    q = jax.random.normal(ks[0], (b, c, nh, hd), dtype)
    kc = jax.random.normal(ks[1], (b, c, nkv, hd), dtype)
    vc = jax.random.normal(ks[2], (b, c, nkv, hd), dtype)
    k_pages = jax.random.normal(ks[3], (P, nkv, pg, hd), dtype)
    v_pages = jax.random.normal(ks[4], (P, nkv, pg, hd), dtype)
    perm = 1 + np.random.default_rng(1).permutation(P - 1)[: b * W]
    tbl = jnp.asarray(perm.reshape(b, W), jnp.int32)
    lengths = jnp.asarray((list(lens) * (1 + b // len(lens)))[:b], jnp.int32)
    creal = jnp.asarray((list(reals) * (1 + b // len(reals)))[:b], jnp.int32)
    return q, kc, vc, k_pages, v_pages, tbl, lengths, creal


def prefill_lax_ref(q, kc, vc, k_pages, v_pages, tbl, lengths, creal):
    """The scatter + gather + masked-SDPA fallback, replicated here so
    the kernel is checked against an INDEPENDENT formulation."""
    b, c, nh, hd = q.shape
    pg = k_pages.shape[2]
    W = tbl.shape[1]
    pad = c - creal
    pos = lengths[:, None] + jnp.arange(c)[None, :] - pad[:, None]
    posc = jnp.maximum(pos, 0)
    real = jnp.arange(c)[None, :] >= pad[:, None]
    pidx = jnp.clip(posc // pg, 0, W - 1)
    phys = jnp.where(real, jnp.take_along_axis(tbl, pidx, axis=1), 0)
    off = jnp.where(real, posc % pg, 0)
    k_pages = k_pages.at[phys, :, off].set(kc.astype(k_pages.dtype))
    v_pages = v_pages.at[phys, :, off].set(vc.astype(v_pages.dtype))
    kk, vv = gather_kv_pages(k_pages, v_pages, tbl)
    out = _sdpa_positions(q, kk, vv, jnp.minimum(posc, W * pg - 1))
    return out, k_pages, v_pages


@pytest.mark.parametrize("case", [
    # ragged mix: fresh row, mid-prefix row, page-straddling row
    dict(lens=(0, 5, 17), reals=(16, 11, 16)),
    # EMPTY row (all-pad chunk on an empty cache) next to live rows
    dict(lens=(0, 9, 0), reals=(0, 16, 7)),
    # chunk straddling a page boundary from inside a page (len=12, pg=8:
    # the write spans pages 1..3 of the row)
    dict(lens=(12,), reals=(16,), b=2),
    # FULL pool: a row whose chunk tops out its very last page
    dict(lens=(48,), reals=(16,), b=2, W=8),
    # MQA + bigger pages
    dict(nh=4, nkv=1, hd=64, pg=16, W=4, lens=(3, 20), reals=(16, 16)),
    # zero-token chunk on a row whose length ends MID-page (the one mix
    # where the straddling live page rides the real-page flush path with
    # nothing to write) next to a normally-writing row
    dict(lens=(12, 4), reals=(0, 16), b=2),
])
def test_prefill_kernel_matches_lax(rng, case):
    q, kc, vc, kp, vp, tbl, lens, creal = prefill_case(rng, **case)
    ref_o, ref_kp, ref_vp = prefill_lax_ref(q, kc, vc, kp, vp, tbl, lens,
                                            creal)
    got_o, got_kp, got_vp = prefill_at(
        q, kc, vc, kp, vp, tbl, lens, creal, interpret=True
    )
    b, c = q.shape[:2]
    pad = np.asarray(c - creal)
    # REAL query positions must match the fallback; pad-query outputs are
    # garbage on both paths (their stream positions are discarded)
    for r in range(b):
        np.testing.assert_allclose(
            np.asarray(got_o)[r, pad[r]:], np.asarray(ref_o)[r, pad[r]:],
            atol=1e-5, rtol=1e-5,
        )
    assert not np.isnan(np.asarray(got_o)).any()
    # the fused write landed the chunk K/V in the SAME page positions the
    # scatter fallback wrote: compare every page either side touched
    pg = kp.shape[2]
    total = np.asarray(lens) + np.asarray(creal)
    for r in range(b):
        for j in range(tbl.shape[1]):
            lo, hi = j * pg, (j + 1) * pg
            if hi <= int(np.asarray(lens)[r]) or lo >= int(total[r]):
                continue  # untouched by this chunk
            p = int(np.asarray(tbl)[r, j])
            w = slice(max(lo, int(np.asarray(lens)[r])) - lo,
                      min(hi, int(total[r])) - lo)
            np.testing.assert_allclose(
                np.asarray(got_kp)[p][:, w], np.asarray(ref_kp)[p][:, w],
                atol=1e-6, rtol=1e-6,
            )
            np.testing.assert_allclose(
                np.asarray(got_vp)[p][:, w], np.asarray(ref_vp)[p][:, w],
                atol=1e-6, rtol=1e-6,
            )


def test_prefill_kernel_preserves_prefix_pages(rng):
    """Pages holding the PREFIX (written by earlier chunks) and pages of
    OTHER rows must come through the fused write byte-identical — the
    trash-page flush routing can never touch a live page it doesn't
    own."""
    q, kc, vc, kp, vp, tbl, lens, creal = prefill_case(
        rng, lens=(24, 3, 0), reals=(16, 13, 16)
    )
    # snapshot before the call: the kernel's aliased page outputs may
    # donate the input buffers
    kp_np, vp_np = np.asarray(kp), np.asarray(vp)
    _, got_kp, got_vp = prefill_at(
        q, kc, vc, kp, vp, tbl, lens, creal, interpret=True
    )
    pg = kp_np.shape[2]
    touched = set()
    for r in range(q.shape[0]):
        ln, tot = int(lens[r]), int(lens[r] + creal[r])
        for j in range(tbl.shape[1]):
            if j * pg + pg > ln and j * pg < tot:
                touched.add(int(tbl[r, j]))
    touched.add(0)  # the trash page eats the no-write flushes
    for p in range(kp_np.shape[0]):
        if p in touched:
            continue
        np.testing.assert_array_equal(np.asarray(got_kp)[p], kp_np[p])
        np.testing.assert_array_equal(np.asarray(got_vp)[p], vp_np[p])


def test_prefill_kernel_zero_chunk_mid_page_flush(rng):
    """chunk_real=0 on a row whose length ends MID-page: ``kv_out_idx``'s
    takes_write is true for the straddling page, so the kernel flushes
    that LIVE page through the real-page path with zero tokens to write
    — the ``written`` mask alone must reproduce its content
    byte-identical (a regression here would corrupt already-written
    prefix KV)."""
    q, kc, vc, kp, vp, tbl, lens, creal = prefill_case(
        rng, b=2, lens=(12, 4), reals=(0, 16)
    )
    kp_np, vp_np = np.asarray(kp), np.asarray(vp)
    _, got_kp, got_vp = prefill_at(
        q, kc, vc, kp, vp, tbl, lens, creal, interpret=True
    )
    pg = kp_np.shape[2]
    # row 0's length 12 ends inside logical page 1 (pg=8): that page is
    # the takes_write-with-nothing-written edge
    p = int(tbl[0, 12 // pg])
    np.testing.assert_array_equal(np.asarray(got_kp)[p], kp_np[p])
    np.testing.assert_array_equal(np.asarray(got_vp)[p], vp_np[p])


def test_prefill_kernel_one_trace_across_ragged_lengths(rng):
    """One jit trace covers every (lengths, chunk_real) mix at a fixed
    (b, c, W) layout — chunk interleaving can never retrace."""
    q, kc, vc, kp, vp, tbl, _, _ = prefill_case(rng)

    fn = jax.jit(
        lambda q, kc, vc, kp, vp, tbl, ln, cr:
        ragged_paged_prefill_attention(q, kc, vc, pool_of(kp), pool_of(vp),
                                       LAYER, tbl, ln, cr, interpret=True)
    )
    before = TRACE_COUNTS["ragged_prefill"]
    for lens, reals in (([0, 0, 0], [16, 16, 16]),
                        ([5, 40, 0], [16, 8, 0]),
                        ([17, 3, 30], [16, 16, 16])):
        out = fn(q, kc, vc, kp, vp, tbl,
                 jnp.asarray(lens, jnp.int32), jnp.asarray(reals, jnp.int32))
        jax.block_until_ready(out)
    assert TRACE_COUNTS["ragged_prefill"] == before + 1


def test_prefill_kernel_tpu_lowering(rng):
    """The REAL Pallas->Mosaic lowering of the prefill kernel (no chip
    needed), including the conditional trash-page output index map and
    the aliased page-pool outputs."""
    b, c, nh, nkv, hd, pg, W, P = 2, 128, 8, 2, 64, 16, 8, 33
    q = jnp.zeros((b, c, nh, hd), jnp.bfloat16)
    kc = jnp.zeros((b, c, nkv, hd), jnp.bfloat16)
    kp = jnp.zeros((LAYERS, P, nkv, pg, hd), jnp.bfloat16)
    tbl = jnp.zeros((b, W), jnp.int32)
    ln = jnp.zeros((b,), jnp.int32)

    def f(q, kc, vc, kp, vp, a, tbl, ln, cr):
        return ragged_paged_prefill_attention(q, kc, vc, kp, vp, a, tbl, ln,
                                              cr, interpret=False)

    exp = jax.export.export(jax.jit(f), platforms=["tpu"])(
        q, kc, kc, kp, kp, jnp.int32(LAYER), tbl, ln, ln
    )
    assert exp.platforms == ("tpu",)


def test_attention_chunk_kernel_path_matches_lax(rng):
    """attn_impl='pallas' routes attention_mixer_chunk through the fused
    prefill kernel and reproduces the lax scatter+gather path — outputs
    AND the resulting page pools (the fused write is the write)."""
    from mamba_distributed_tpu.config import ModelConfig
    from mamba_distributed_tpu.models.attention import (
        attention_mixer_chunk,
        init_attention_params,
        init_attention_state,
        attention_page_meta,
    )

    kw = dict(d_model=64, n_layer=2, vocab_size=64, ssm_layer="mamba2",
              headdim=32, d_state=32, chunk_size=16,
              compute_dtype="float32", attn_layer_idx=(1,),
              attn_num_heads=4, attn_num_kv_heads=2, remat=False,
              prefill_chunk_tokens=16, kv_page_tokens=8, kv_slot_tokens=64)
    cfg_x = ModelConfig(**kw)
    cfg_p = ModelConfig(**kw, attn_impl="pallas")
    params = init_attention_params(rng, cfg_x)
    b, c = 3, 16
    kv = jax.tree.map(pool_of, init_attention_state(cfg_x, b, 64))
    tbl, _ = attention_page_meta(cfg_x, b, 64)
    lengths = jnp.asarray([0, 5, 12], jnp.int32)
    u = jax.random.normal(jax.random.fold_in(rng, 1), (b, c, 64),
                          jnp.float32)
    # ragged per-row masks: row 0 half-pad, row 1 full, row 2 full
    mask = jnp.asarray(
        [[0.0] * 8 + [1.0] * 8, [1.0] * 16, [1.0] * 16], jnp.float32
    )
    # seed the pool through one lax chunk first (both paths identically)
    _, kv = attention_mixer_chunk(params, cfg_x, u, kv, LAYER, tbl, lengths,
                                  token_mask=None)
    lengths = lengths + c
    y_ref, kv_ref = attention_mixer_chunk(params, cfg_x, u + 1.0, kv, LAYER,
                                          tbl, lengths, token_mask=mask)
    y_pal, kv_pal = attention_mixer_chunk(params, cfg_p, u + 1.0, kv, LAYER,
                                          tbl, lengths, token_mask=mask)
    pad = np.asarray(c - mask.sum(axis=1), np.int32)
    for r in range(b):
        np.testing.assert_allclose(
            np.asarray(y_pal)[r, pad[r]:], np.asarray(y_ref)[r, pad[r]:],
            atol=1e-5, rtol=1e-5,
        )
    # identity tables never touch the trash page, so the pools must agree
    # everywhere except page 0 (the kernel's no-write flush target)
    for a, c_ in zip(kv_pal, kv_ref):
        np.testing.assert_allclose(np.asarray(a)[:, 1:],
                                   np.asarray(c_)[:, 1:],
                                   atol=1e-6, rtol=1e-6)


def test_page_recycle_no_alias_head_major(rng):
    """Page-recycle aliasing under the head-major layout: a page freed
    by one row and handed to another must read back exactly what the new
    owner wrote — decode over recycled pages matches a fresh pool."""
    S, nh, nkv, hd, pg, W, P = 2, 4, 2, 32, 8, 2, 5
    ks = jax.random.split(rng, 4)
    q = jax.random.normal(ks[0], (S, nh, hd))
    kv_len = jnp.asarray([14, 0], jnp.int32)

    # row 0 owned pages {1, 2}; it was evicted and row 1 recycled them —
    # then wrote 14 tokens of its own K/V through the chunk writer
    fresh_k = jax.random.normal(ks[1], (P, nkv, pg, hd))
    fresh_v = jax.random.normal(ks[2], (P, nkv, pg, hd))
    kc = jax.random.normal(ks[3], (1, 16, nkv, hd))
    tbl_new = jnp.asarray([[1, 2], [0, 0]], jnp.int32)

    def write(pages, chunk):
        pos = jnp.arange(16)
        phys = jnp.where(pos < 14, tbl_new[0][jnp.clip(pos // pg, 0, 1)], 0)
        off = jnp.where(pos < 14, pos % pg, 0)
        return pages.at[phys, :, off].set(chunk[0])

    # stale pool: pages 1/2 still hold the EVICTED row's garbage under
    # the new writes at positions >= 14 — exactly the recycle state
    stale_k = write(fresh_k, kc)
    stale_v = write(fresh_v, kc * 0.5)
    clean_k = write(jnp.zeros_like(fresh_k), kc)
    clean_v = write(jnp.zeros_like(fresh_v), kc * 0.5)

    got_stale = decode_at(
        q, stale_k, stale_v, tbl_new, kv_len, interpret=True
    )
    got_clean = decode_at(
        q, clean_k, clean_v, tbl_new, kv_len, interpret=True
    )
    # positions < 14 were overwritten by the new owner; >= 14 are masked
    # by kv_len — stale residue is invisible
    np.testing.assert_array_equal(np.asarray(got_stale),
                                  np.asarray(got_clean))


# ------------------------------------------- a layer of the pool, by index


@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
def test_kernels_address_a_layer_of_the_pool(rng, dtype):
    """Both kernels on a three-layer pool, at each layer index handed over
    as a TRACED scalar (what the group scan does): bit for bit the call on
    that layer's slice alone; the prefill kernel's write changes the
    indexed layer's owned pages and its trash page, nothing else in the
    pool; and one trace serves every layer index."""
    quant = dtype == "int8"
    act = jnp.float32 if quant else jnp.bfloat16
    b, c, nh, nkv, hd, pg, W, P = 3, 16, 8, 2, 32, 8, 4, 17
    ks = jax.random.split(rng, 8)
    if quant:
        pages = lambda k: jax.random.randint(
            k, (LAYERS, P, nkv, pg, hd), -127, 128).astype(jnp.int8)
        scales = lambda k: 0.001 + 0.05 * jax.random.uniform(
            k, (LAYERS, P, nkv), jnp.float32)
        k_old, v_old = scales(ks[5]), scales(ks[6])
        k_new, v_new = 1.5 * k_old, 1.25 * v_old
    else:
        pages = lambda k: jax.random.normal(k, (LAYERS, P, nkv, pg, hd), act)
        k_old = v_old = k_new = v_new = None
    kp, vp = pages(ks[0]), pages(ks[1])
    q = jax.random.normal(ks[2], (b, c, nh, hd), act)
    kc = jax.random.normal(ks[3], (b, c, nkv, hd), act)
    vc = jax.random.normal(ks[4], (b, c, nkv, hd), act)
    perm = 1 + np.random.default_rng(2).permutation(P - 1)[: b * W]
    tbl = jnp.asarray(perm.reshape(b, W), jnp.int32)
    lens = jnp.asarray([0, 5, 12], jnp.int32)
    creal = jnp.asarray([16, 11, 16], jnp.int32)
    at = lambda x, a: None if x is None else x[a]

    def decode(kp, vp, a, k_s, v_s):
        return ragged_paged_decode_attention(
            q[:, 0], kp, vp, a, tbl, lens + creal,
            k_scale=k_s, v_scale=v_s, interpret=True)

    def prefill(kp, vp, a, k_o, v_o, k_n, v_n):
        kw = dict(k_scale_old=k_o, v_scale_old=v_o,
                  k_scale_new=k_n, v_scale_new=v_n) if quant else {}
        return ragged_paged_prefill_attention(
            q, kc, vc, kp, vp, a, tbl, lens, creal, interpret=True, **kw)

    # the layer's scales are sliced by the caller (32 KB a layer at the
    # benchmark's pool); the pages never are
    decode_jit = jax.jit(
        lambda a: decode(kp, vp, a, at(k_old, a), at(v_old, a)))
    prefill_jit = jax.jit(
        lambda a: prefill(kp, vp, a, at(k_old, a), at(v_old, a),
                          at(k_new, a), at(v_new, a)))
    owned = {0}  # the trash page eats the no-write flushes
    for r in range(b):
        for j in range(W):
            if j * pg + pg > int(lens[r]) and j * pg < int(lens[r] + creal[r]):
                owned.add(int(tbl[r, j]))
    others = sorted(set(range(P)) - owned)
    before = dict(TRACE_COUNTS)
    got = [(decode_jit(jnp.int32(a)), prefill_jit(jnp.int32(a)))
           for a in range(LAYERS)]
    assert TRACE_COUNTS["ragged_decode"] == before["ragged_decode"] + 1
    assert TRACE_COUNTS["ragged_prefill"] == before["ragged_prefill"] + 1
    eq = lambda x, y: np.testing.assert_array_equal(
        np.asarray(x, np.float32), np.asarray(y, np.float32))
    for a, (dec, (o, kpool, vpool)) in enumerate(got):
        one = lambda x: x[a][None]
        eq(dec, decode(one(kp), one(vp), 0, at(k_old, a), at(v_old, a)))
        ref_o, ref_k, ref_v = prefill(
            one(kp), one(vp), 0, at(k_old, a), at(v_old, a),
            at(k_new, a), at(v_new, a))
        eq(o, ref_o)
        for pool, ref, src in ((kpool, ref_k, kp), (vpool, ref_v, vp)):
            eq(pool[a, 1:], ref[0, 1:])
            eq(pool[a][jnp.asarray(others)], src[a][jnp.asarray(others)])
            for other in set(range(LAYERS)) - {a}:
                eq(pool[other], src[other])
