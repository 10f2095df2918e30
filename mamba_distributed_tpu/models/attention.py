"""Causal self-attention with RoPE (hybrid Jamba-style layers).

Functional equivalent of ``mamba_ssm.modules.mha.MHA`` as used by hybrid
configs via ``attn_layer_idx``/``attn_cfg`` (mamba-ssm 2.2.2; the reference
never enables it — SURVEY.md §2.3 — but BASELINE.json config 5 requires it).

GQA layout: packed qkv projection, ``num_heads`` query heads sharing
``num_kv_heads`` KV heads; rotary embedding on the leading ``rotary_dim``
of each head.  Under sequence parallelism the score/value contraction runs
as ring attention over the mesh's ``seq`` axis (parallel/ring_attention.py).

Decode state is a PAGED KV cache with per-row lengths (the ragged/paged
attention pattern — see the section marker below): rows of one decode
batch may sit at different sequence positions, which is what admits
hybrid models into the serving slot pool (serving/state_cache.py).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from mamba_distributed_tpu.config import ModelConfig
from mamba_distributed_tpu.models.common import init_linear, linear
from mamba_distributed_tpu.obs import scopes


def _attn_dims(cfg: ModelConfig):
    nh = cfg.effective_attn_num_heads
    nkv = cfg.effective_attn_num_kv_heads
    hd = cfg.effective_attn_head_dim
    # -1 => full head dim; 0 => no rotary (mamba_ssm's rotary_emb_dim)
    rot = hd if cfg.attn_rotary_dim < 0 else cfg.attn_rotary_dim
    return nh, nkv, hd, rot


def init_attention_params(key: jax.Array, cfg: ModelConfig) -> dict:
    nh, nkv, hd, _ = _attn_dims(cfg)
    k_qkv, k_out = jax.random.split(key)
    params = {
        "wqkv": init_linear(k_qkv, cfg.d_model, (nh + 2 * nkv) * hd, cfg.proj_bias),
        "out_proj": init_linear(k_out, nh * hd, cfg.d_model, cfg.proj_bias),
    }
    if cfg.rescale_prenorm_residual:
        n_residuals = 2 if cfg.d_intermediate > 0 else 1
        params["out_proj"]["kernel"] = params["out_proj"]["kernel"] / math.sqrt(
            n_residuals * cfg.n_layer
        )
    return params


def rope_angles(positions: jax.Array, rotary_dim: int, theta: float) -> jax.Array:
    """(t,) or (b, t) int positions -> positions.shape + (rotary_dim/2,)
    angles.  Per-ROW positions are what lets slots at different sequence
    positions share one decode batch (the paged-KV serving pool)."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim)
    )
    return positions.astype(jnp.float32)[..., None] * inv_freq


def apply_rope(x: jax.Array, angles: jax.Array) -> jax.Array:
    """Rotate the leading ``2*angles.shape[-1]`` channels of each head.

    x (b, t, h, hd); angles (t, rot/2) shared across the batch, or
    (b, t, rot/2) per-row (paged decode: every row sits at its own
    position).  Rotate-half (GPT-NeoX, non-interleaved) convention on
    the rotary slice — pairs are (x[i], x[i + rot/2]) — matching the
    flash-attn RotaryEmbedding default (``interleaved=False``) that
    mamba_ssm's MHA layers use, so hybrid checkpoints import with
    bit-compatible attention semantics.  The tail past the rotary slice
    passes through.
    """
    rot = 2 * angles.shape[-1]
    xr, x_pass = x[..., :rot], x[..., rot:]
    xf = xr.astype(jnp.float32)
    x1, x2 = xf[..., : rot // 2], xf[..., rot // 2 :]
    if angles.ndim == 2:
        angles = angles[None]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    o1 = x1 * cos - x2 * sin
    o2 = x1 * sin + x2 * cos
    out = jnp.concatenate([o1, o2], axis=-1).astype(x.dtype)
    return jnp.concatenate([out, x_pass], axis=-1) if x_pass.size else out


def _split_qkv(qkv: jax.Array, cfg: ModelConfig):
    nh, nkv, hd, _ = _attn_dims(cfg)
    b, t, _ = qkv.shape
    q = qkv[..., : nh * hd].reshape(b, t, nh, hd)
    k = qkv[..., nh * hd : (nh + nkv) * hd].reshape(b, t, nkv, hd)
    v = qkv[..., (nh + nkv) * hd :].reshape(b, t, nkv, hd)
    if cfg.key_multiplier != 1.0:
        # a published scalar on the keys (before RoPE, which is linear)
        k = (k.astype(jnp.float32) * cfg.key_multiplier).astype(k.dtype)
    if cfg.attention_multiplier:
        # a STATED softmax scale: every attention path below divides its
        # scores by sqrt(hd), so the queries carry the stated scale times
        # sqrt(hd) (before RoPE, which is linear; q is never cached)
        q = (q.astype(jnp.float32)
             * (cfg.attention_multiplier * math.sqrt(hd))).astype(q.dtype)
    return q, k, v


def _sdpa_causal(q, k, v, offset: int = 0):
    """Causal softmax(QK^T/sqrt(d))V with GQA broadcast, fp32 softmax.

    q (b, tq, nh, hd); k/v (b, tk, nkv, hd); ``offset`` = absolute position
    of q[0] minus that of k[0] (for decode with cache).
    """
    b, tq, nh, hd = q.shape
    nkv = k.shape[2]
    rep = nh // nkv
    qh = q.reshape(b, tq, nkv, rep, hd)
    scores = jnp.einsum(
        "bqgrh,bkgh->bgrqk", qh, k, preferred_element_type=jnp.float32
    ) / math.sqrt(hd)
    qpos = jnp.arange(tq)[:, None] + offset
    kpos = jnp.arange(k.shape[1])[None, :]
    scores = jnp.where(qpos >= kpos, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bgrqk,bkgh->bqgrh", w, v, preferred_element_type=jnp.float32)
    return out.reshape(b, tq, nh, hd).astype(q.dtype)


def attention_mixer(
    params: dict,
    cfg: ModelConfig,
    u: jax.Array,
    initial_state=None,
    return_final_state: bool = False,
    seq_ctx=None,
):
    """Full-sequence causal attention.  u (b, t, d) -> (b, t, d).

    With ``return_final_state`` the raw (k, v) of the whole sequence are
    returned alongside; the caller (models/lm.lm_prefill) packs them into
    the paged decode cache (``pack_attention_pages``).
    """
    nh, nkv, hd, rot = _attn_dims(cfg)
    b, t, _ = u.shape
    compute_dtype = jnp.dtype(cfg.compute_dtype)

    with jax.named_scope(scopes.ATTN_QKV):
        qkv = linear(params["wqkv"], u, compute_dtype)
        q, k, v = _split_qkv(qkv, cfg)
        if rot > 0:
            angles = rope_angles(jnp.arange(t), rot, cfg.rope_theta)
            q = apply_rope(q, angles)
            k = apply_rope(k, angles)

    from mamba_distributed_tpu.ops.pallas.common import resolve_attn_impl

    attn_impl = resolve_attn_impl(cfg.attn_impl)
    with jax.named_scope(scopes.ATTN_KERNEL):
        if seq_ctx is not None:
            if cfg.attn_sp_impl == "ulysses":
                from mamba_distributed_tpu.parallel.ulysses import (
                    ulysses_attention,
                )

                out = ulysses_attention(seq_ctx, q, k, v, impl=attn_impl)
            else:
                from mamba_distributed_tpu.parallel.ring_attention import (
                    ring_attention,
                )

                out = ring_attention(seq_ctx, q, k, v, impl=attn_impl)
        elif attn_impl == "pallas":
            from mamba_distributed_tpu.ops.pallas.attention_kernels import (
                flash_sdpa_causal,
            )

            # flash kernel: online softmax in VMEM, fully-future blocks
            # skipped
            out = flash_sdpa_causal(q, k, v)
        else:
            from mamba_distributed_tpu.ops.blockwise_attention import (
                blockwise_sdpa_causal,
            )

            # O(t*block) memory — never materializes the (t, t) score
            # tensor (config 5 at T=8192); the tiny-t paged decode path
            # keeps the explicit-mask _sdpa_positions
            out = blockwise_sdpa_causal(q, k, v)
    # remat_policy="mixer" save point (models/lm.py:_remat)
    out = checkpoint_name(out, "mixer_out")
    with jax.named_scope(scopes.ATTN_OUT):
        y = linear(
            params["out_proj"], out.reshape(b, t, nh * hd), compute_dtype
        )
    if return_final_state:
        return y, (k, v)
    return y


# ---------------------------------------------------------------------------
# Paged decode-time KV cache ("Ragged Paged Attention", PAPERS.md)
#
# The decode cache is a pool of fixed-size pages plus per-ROW metadata:
#
#   k_pages / v_pages  (P, nkv, page, hd)   physical pages, HEAD-MAJOR;
#                                           page 0 is a reserved trash
#                                           page that masked-out rows
#                                           write into
#
# Head-major storage is the kernel-native layout: the Pallas ragged
# kernels (ops/pallas/attention_kernels.py) block pages as (page, hd)
# tiles per (page, kv-head) cell, so storing (nkv, page, hd) lets the
# BlockSpec index map address a page's head slice directly — no per-call
# transpose of the whole pool on the decode/prefill hot path.  The lax
# fallback pays one extra axis move inside its (already materializing)
# gather instead.
#   page_table         (b, W) int32         row r's logical page j lives
#                                           in physical page table[r, j]
#   lengths            (b,) int32           tokens cached per row
#
# Rows at DIFFERENT sequence positions share one batch (per-row RoPE
# angles, per-row causal masks, per-row scatter writes), which is what
# lets hybrid models into the serving slot pool (serving/state_cache.py);
# KV HBM is O(pages in use) because pages are handed out by a host-side
# allocator on admission and recycled on evict.  ``generate()`` uses the
# same structure with an identity table — the SAME decode step serves
# both, which is what keeps engine<->generate() token parity exact.
#
# Bit-stability note: masked attention over a zero-padded key axis is
# bit-identical across padded widths at 8-lane granularity (verified on
# CPU XLA; cfg enforces kv_page_tokens % 8 == 0), so the engine's
# page-count bucket may differ from generate()'s without perturbing
# token streams.
# ---------------------------------------------------------------------------


def attention_page_count(cfg: ModelConfig, max_len: int) -> int:
    """Pages needed per row for ``max_len`` tokens (at least one)."""
    return max(1, -(-max_len // cfg.kv_page_tokens))


# ---------------------------------------------------------------------------
# Int8 KV page quantization (cfg.kv_page_dtype == "int8"; ops/quant.py
# holds the shared round/clip math and docs/SERVING.md "Quantized
# serving" the layout).  An int8 layer cache is a 4-tuple
# ``(k_pages int8, v_pages int8, k_scale f32 (P, nkv), v_scale f32
# (P, nkv))`` — one symmetric scale per (physical page, kv head), so a
# page's whole (page, hd) tile dequantizes with ONE scalar multiply
# (what the Pallas page walk fuses in-register).  The scale-update rule
# needs NO read of old page content:
#
#   new_scale = max(old_scale if the page holds PRIOR tokens of this
#                   sequence (write offset > 0 within the page),
#                   absmax(fresh rows) / 127)
#
# because old_scale already bounds the page's stored values.  Old rows
# re-express under the new scale (``round(q_old * old/new)`` — the
# ratio is <= 1 whenever prior content exists, so requantization only
# ever rounds, never clips real data), and a RECYCLED page's stale
# scale is ignored outright (no prior content => fresh scale), so
# garbage from an evicted tenant can never inflate a live page's step
# size.  The lax fallback and both ragged kernels implement the same
# rule, so kernel-vs-lax stays within fp tolerance at every ragged mix.
# ---------------------------------------------------------------------------


def _kv_page_scale_init(n_pages: int, nkv: int) -> jax.Array:
    """Fresh scale array: ones — never read before the first write to a
    page sets it (the no-prior-content branch ignores old scales), and
    finite so trash-page dequantization can never produce NaN/inf."""
    return jnp.ones((n_pages, nkv), jnp.float32)


def init_attention_state(cfg: ModelConfig, batch: int, max_len: int,
                         dtype=None):
    """Empty paged KV cache for one attention layer: (k_pages, v_pages)
    of shape (1 + batch*W, nkv, page, hd) — HEAD-MAJOR, page 0 is the
    trash page — in the compute dtype, matching what the prefill path
    produces.  The shared (page_table, lengths) metadata is built once
    per model by ``attention_page_meta`` (models/lm.init_lm_state).

    ``cfg.kv_page_dtype="int8"`` returns the quantized 4-tuple instead:
    int8 pages plus the per-(page, kv-head) f32 scale arrays (see the
    section comment above) — page bytes halve, which is the serving
    pool's capacity doubling (``quant_kv_capacity``)."""
    nh, nkv, hd, _ = _attn_dims(cfg)
    quant = cfg.kv_quantized and dtype is None
    if dtype is None:
        dtype = jnp.int8 if quant else jnp.dtype(cfg.compute_dtype)
    W = attention_page_count(cfg, max_len)
    P = 1 + batch * W
    shape = (P, nkv, cfg.kv_page_tokens, hd)
    # two INDEPENDENT allocations: returning one aliased array twice
    # would blow up any donating jit downstream ("donate the same
    # buffer twice") if a caller ever skips the re-stacking copy
    if quant:
        return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                _kv_page_scale_init(P, nkv), _kv_page_scale_init(P, nkv))
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def attention_page_meta(cfg: ModelConfig, batch: int, max_len: int):
    """Identity page table + zero lengths for a private (non-pooled)
    paged cache: row r owns physical pages [1 + r*W, 1 + (r+1)*W)."""
    W = attention_page_count(cfg, max_len)
    tbl = 1 + jnp.arange(batch * W, dtype=jnp.int32).reshape(batch, W)
    return tbl, jnp.zeros((batch,), jnp.int32)


def pack_attention_pages(cfg: ModelConfig, k: jax.Array, v: jax.Array,
                         max_len: int):
    """(b, t, nkv, hd) full-sequence K/V -> identity-paged head-major
    (k_pages, v_pages) with capacity ``max_len`` (lm_prefill's state
    packing).  Int8 pools additionally quantize each (page, kv-head)
    tile under its own absmax scale and return the 4-tuple."""
    b, t, nkv, hd = k.shape
    pg = cfg.kv_page_tokens
    W = attention_page_count(cfg, max_len)

    def pack(x):
        x = jnp.pad(x, ((0, 0), (0, W * pg - t), (0, 0), (0, 0)))
        x = x.reshape(b, W, pg, nkv, hd)
        x = jnp.moveaxis(x, 3, 2).reshape(b * W, nkv, pg, hd)
        return jnp.concatenate([jnp.zeros_like(x[:1]), x], axis=0)

    if cfg.kv_quantized:
        from mamba_distributed_tpu.ops.quant import (
            Q_MAX,
            SCALE_EPS,
            kv_quantize,
        )

        def pack_q(x):
            pages = pack(x.astype(jnp.float32))           # (P, nkv, pg, hd)
            absmax = jnp.max(jnp.abs(pages), axis=(2, 3))  # (P, nkv)
            scale = jnp.maximum(absmax / Q_MAX, SCALE_EPS)
            q = kv_quantize(pages, scale[:, :, None, None])
            return q.astype(jnp.int8), scale

        kq, ks = pack_q(k)
        vq, vs = pack_q(v)
        return kq, vq, ks, vs
    return pack(k), pack(v)


def gather_kv_pages(k_pages: jax.Array, v_pages: jax.Array,
                    page_table: jax.Array,
                    live_pages: jax.Array | None = None,
                    k_scale: jax.Array | None = None,
                    v_scale: jax.Array | None = None,
                    dtype=None, layer=None):
    """Reassemble each row's logical KV view: (P, nkv, pg, hd) head-major
    pages + (b, W) table -> (b, W*pg, nkv, hd).  With ``layer`` (an int
    or a traced scalar) the pages are the whole (A, P, nkv, pg, hd) pool
    and the gather reads that layer's pages out of it, so no slice of
    the pool is ever made; the scales stay the layer's own (P, nkv).
    The lax fallback path —
    the Pallas ragged kernels (ops/pallas/attention_kernels.py) walk the
    table in-kernel instead of materializing this (and read the
    head-major pages without the axis move this gather folds in).

    ``live_pages`` (b,) int32 — logical pages actually LIVE per row —
    redirects table entries at or past each row's live extent to the
    trash page, so the gather's read traffic touches only live pages
    (plus the one trash page, hot in cache) instead of every reserved
    page up to the table width: O(live tokens), not O(pool), per call —
    what makes the fallback viable for CPU-serving deployments.  Safe
    bit-exactly: every position in a dead page is already hard-masked
    to -inf by the callers' causal/position bounds (``_sdpa_positions``
    ``jnp.where``s masked scores regardless of the gathered values), so
    the substitution can never change a live lane.

    ``k_scale``/``v_scale`` (int8 pools: (P, nkv) per-page-per-head
    scales) dequantize the gathered pages into ``dtype`` — the lax
    mirror of the kernels' in-register scale multiply.  Trash-page
    rows dequantize with the trash scale (finite garbage, masked as
    above)."""
    b, W = page_table.shape
    nkv, pg, hd = k_pages.shape[-3:]
    if live_pages is not None:
        page_table = jnp.where(
            jnp.arange(W)[None, :] < live_pages[:, None], page_table, 0
        )
    if dtype is None:
        dtype = jnp.float32

    def gather(pages, scales):
        # (b, W, nkv, pg, hd)
        x = pages[page_table] if layer is None else pages[layer, page_table]
        if scales is not None:
            x = x.astype(dtype) * scales[page_table][
                ..., None, None].astype(dtype)
        x = jnp.moveaxis(x, 2, 3)                        # (b, W, pg, nkv, hd)
        return x.reshape(b, W * pg, nkv, hd)

    return gather(k_pages, k_scale), gather(v_pages, v_scale)


def _sdpa_positions(q, k, v, qpos):
    """Masked SDPA with per-row absolute query positions.

    q (b, tq, nh, hd); k/v (b, L, nkv, hd) — the gathered logical cache
    view; qpos (b, tq) int32 — query i of row r may attend cache
    position j iff ``j <= qpos[r, i]`` (the cache holds positions
    [0, lengths) plus this call's freshly written tokens, so the bound
    is exactly the causal rule).
    """
    b, tq, nh, hd = q.shape
    nkv = k.shape[2]
    rep = nh // nkv
    qh = q.reshape(b, tq, nkv, rep, hd)
    scores = jnp.einsum(
        "bqgrh,bkgh->bgrqk", qh, k, preferred_element_type=jnp.float32
    ) / math.sqrt(hd)
    kpos = jnp.arange(k.shape[1])
    mask = qpos[:, None, None, :, None] >= kpos[None, None, None, None, :]
    scores = jnp.where(mask, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bgrqk,bkgh->bqgrh", w, v, preferred_element_type=jnp.float32)
    return out.reshape(b, tq, nh, hd).astype(q.dtype)


def _layer_scales(scales, layer):
    """One attention layer's (P, nkv) scales out of the (A, P, nkv) leaf
    (int8 pools; 32 KB at the benchmark's pool, where a layer of pages
    is 67 MB and is never sliced)."""
    return jax.lax.dynamic_index_in_dim(scales, layer, 0, keepdims=False)


def attention_mixer_step(params: dict, cfg: ModelConfig, u_t: jax.Array,
                         kv, layer, page_table: jax.Array,
                         lengths: jax.Array,
                         write_mask: jax.Array | None = None):
    """Single-token decode against the paged KV cache.

    u_t (b, d); kv = (k_pages, v_pages) — the WHOLE page pools
    (A, P, nkv, pg, hd), every attention layer's, as the caller's layer
    loop carries them — or the int8 4-tuple with the (A, P, nkv)
    per-(page, kv-head) scales; ``layer`` — this layer's index into
    them, a traced scalar under a scan or an int when unrolled: the one
    row a slot writes is scattered into the pool at ``[layer, phys, :,
    off]`` and the kernel addresses the layer by index, so nothing the
    size of the pool, or of a layer of it, is made on the way;
    page_table (b, W); lengths (b,)
    — the row's token count BEFORE this step (the new token lands at
    cache position ``lengths[r]``).  ``write_mask`` (b,) bool routes
    masked rows' KV writes to the trash page and is how the serving tick
    protects recycled pages from dead slots; the shared ``lengths``
    update happens once per model step in models/lm.py.

    Int8 pools make the write page-granular: the target page is read,
    old rows re-expressed under the (possibly grown) scale, the fresh
    row quantized in, and the (page, scale) pair scattered back — the
    scale-update rule in the section comment above, shared bit-for-bit
    with the chunk path and mirrored by the kernels.  Masked rows'
    page AND scale writes land on the trash page as before.

    Returns (y (b, d), kv') with kv' the same arity as ``kv``: the whole
    pools again, this layer's rows written.
    """
    nh, nkv, hd, rot = _attn_dims(cfg)
    b, _ = u_t.shape
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    quant = len(kv) == 4
    if quant:
        k_pages, v_pages, k_scale, v_scale = kv
    else:
        k_pages, v_pages = kv
        k_scale = v_scale = None
    pg = cfg.kv_page_tokens
    W = page_table.shape[1]

    with jax.named_scope(scopes.ATTN_QKV):
        qkv = linear(params["wqkv"], u_t[:, None, :], compute_dtype)
        q, k, v = _split_qkv(qkv, cfg)
        if rot > 0:
            angles = rope_angles(lengths[:, None], rot, cfg.rope_theta)
            q = apply_rope(q, angles)
            k = apply_rope(k, angles)

    mask = (
        jnp.ones((b,), bool) if write_mask is None else write_mask
    )
    pidx = jnp.clip(lengths // pg, 0, W - 1)
    phys = jnp.where(
        mask, jnp.take_along_axis(page_table, pidx[:, None], axis=1)[:, 0], 0
    )
    off = jnp.where(mask, lengths % pg, 0)
    if quant:
        from mamba_distributed_tpu.ops.quant import (
            Q_MAX,
            SCALE_EPS,
            kv_quantize,
            kv_requant,
        )

        def qwrite(pages, scales, row):
            # row (b, nkv, hd): requantize the whole target page under
            # the updated scale, insert the fresh row at ``off``
            old_q = pages[layer, phys]                # (b, nkv, pg, hd)
            old_s = scales[layer, phys]               # (b, nkv)
            has_prior = (off > 0)[:, None]            # page holds this
            # sequence's earlier tokens iff the write offset is interior
            amax = jnp.max(jnp.abs(row.astype(jnp.float32)), axis=-1)
            new_s = jnp.maximum(jnp.maximum(
                jnp.where(has_prior, old_s, 0.0), amax / Q_MAX), SCALE_EPS)
            ratio = jnp.where(has_prior, old_s / new_s, 0.0)
            req = kv_requant(old_q, ratio[..., None, None])
            q_row = kv_quantize(row, new_s[..., None])
            onehot = jnp.arange(pg)[None, :] == off[:, None]   # (b, pg)
            page = jnp.where(onehot[:, None, :, None],
                             q_row[:, :, None, :], req)
            return (pages.at[layer, phys].set(page.astype(pages.dtype)),
                    scales.at[layer, phys].set(new_s))

        with jax.named_scope(scopes.KV_WRITE):
            k_pages, k_scale = qwrite(k_pages, k_scale, k[:, 0])
            v_pages, v_scale = qwrite(v_pages, v_scale, v[:, 0])
    else:
        # one (hd,) row per (slot, kv head), scattered into the carried
        # pool at [layer, phys, head, off].  Row by row, not a (nkv, hd)
        # block per slot: a window over the head axis, which is not the
        # pool's minor one, makes the TPU compiler re-lay the whole pool
        # out around the scatter (and back for the kernel, 2 x 537 MB a
        # layer at the benchmark's hybrid); a window of the minor axis
        # alone is written in place, in the kernels' own row-major layout
        with jax.named_scope(scopes.KV_WRITE):
            hh = jnp.arange(nkv)[None, :]
            k_pages = k_pages.at[layer, phys[:, None], hh, off[:, None]].set(
                k[:, 0].astype(k_pages.dtype))
            v_pages = v_pages.at[layer, phys[:, None], hh, off[:, None]].set(
                v[:, 0].astype(v_pages.dtype))
    if quant:
        ks, vs = _layer_scales(k_scale, layer), _layer_scales(v_scale, layer)
    else:
        ks = vs = None

    from mamba_distributed_tpu.ops.pallas.common import resolve_attn_impl

    qpos = jnp.minimum(lengths, W * pg - 1)
    with jax.named_scope(scopes.ATTN_KERNEL):
        if resolve_attn_impl(cfg.attn_impl) == "pallas":
            from mamba_distributed_tpu.ops.pallas.attention_kernels import (
                ragged_paged_decode_attention,
            )

            # kv_len = tokens readable AFTER the write; the kernel skips
            # whole pages past it, so decode cost tracks live tokens
            # (int8 pools: dequant fused into the page walk via the
            # prefetched scales)
            out = ragged_paged_decode_attention(
                q[:, 0], k_pages, v_pages, layer, page_table,
                jnp.minimum(qpos + 1, W * pg),
                k_scale=ks, v_scale=vs,
            )[:, None]
        else:
            # tokens readable after the write = qpos + 1 per row: gather
            # only the pages that hold them (the rest go to trash —
            # masked anyway), so decode cost tracks live tokens off-TPU
            # too
            kk, vv = gather_kv_pages(
                k_pages, v_pages, page_table, (qpos + pg) // pg,
                k_scale=ks, v_scale=vs, dtype=compute_dtype, layer=layer,
            )
            out = _sdpa_positions(q, kk, vv, qpos[:, None])
    with jax.named_scope(scopes.ATTN_OUT):
        y = linear(
            params["out_proj"], out.reshape(b, nh * hd), compute_dtype
        )
    if quant:
        return y, (k_pages, v_pages, k_scale, v_scale)
    return y, (k_pages, v_pages)


def _chunk_page_scales(k, v, real, page_table, lengths, n_real,
                       k_scale, v_scale, pg: int):
    """Post-chunk-write per-(page, kv-head) scales (int8 pools).

    Applies the scale-update rule (section comment above) to every page
    in the chunk's write window — ``[lengths, lengths + n_real)`` per
    row — WITHOUT reading page content: old scales bound old values, so
    ``new = max(old if prior content else 0, chunk absmax / 127)``.
    Returns ``(k_scale', v_scale', takes)`` with the updated (P, nkv)
    arrays (non-window pages untouched; trash-page entries are garbage
    by the usual contract) and the (b, W) write-window mask.  Shared by
    the lax fallback and the Pallas path (the kernel takes the OLD and
    NEW arrays scalar-prefetched and re-derives the requant ratio per
    visited page), so the two paths can never disagree on a scale.
    """
    from mamba_distributed_tpu.ops.quant import Q_MAX, SCALE_EPS

    b, c = real.shape
    W = page_table.shape[1]
    total = lengths + n_real
    pad = c - n_real
    pos = lengths[:, None] + jnp.arange(c)[None, :] - pad[:, None]
    pageidx = jnp.clip(jnp.maximum(pos, 0) // pg, 0, W - 1)
    wcol = jnp.arange(W)[None, :]
    takes = ((wcol * pg < total[:, None])
             & ((wcol + 1) * pg > lengths[:, None])
             & (n_real > 0)[:, None])                      # (b, W)
    has_prior = lengths[:, None] > wcol * pg               # (b, W)
    # which chunk rows land in which window page (pads excluded)
    oh = (pageidx[:, :, None] == wcol[:, None, :]) & real[:, :, None]

    def update(x, scales):
        absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)  # (b,c,nkv)
        amax = jnp.max(
            jnp.where(oh[..., None], absmax[:, :, None, :], 0.0), axis=1
        )                                                  # (b, W, nkv)
        old = scales[page_table]                           # (b, W, nkv)
        new = jnp.maximum(jnp.maximum(
            jnp.where(has_prior[..., None], old, 0.0), amax / Q_MAX),
            SCALE_EPS)
        new = jnp.where(takes[..., None], new, old)
        dst = jnp.where(takes, page_table, 0)              # no-writes -> trash
        return scales.at[dst].set(new)

    return update(k, k_scale), update(v, v_scale), takes


def attention_mixer_chunk(params: dict, cfg: ModelConfig, u: jax.Array,
                          kv, layer, page_table: jax.Array,
                          lengths: jax.Array,
                          token_mask: jax.Array | None = None):
    """One prefill CHUNK against the paged cache: write the chunk's real
    tokens' K/V into this row's pages at positions [lengths, lengths +
    n_real), then attend every chunk query over the page view (prefix +
    the freshly written chunk — intra-chunk causality falls out of the
    per-position bound).

    ``kv`` is the whole page pools (the int8 4-tuple with the scales) and
    ``layer`` this layer's index into them, as in
    ``attention_mixer_step``: the pools come back whole, written in place.

    u (b, c, d); token_mask (b, c) {0,1} marks real tokens — the pad is
    a LEFT prefix (serving/prefill.chunk_inputs), so real token j of the
    chunk sits at absolute position ``lengths[r] + j`` regardless of the
    pad, and pad queries (clamped to position 0) produce garbage that
    dies with their discarded stream positions.  The shared ``lengths``
    advance (+ n_real) happens once per model chunk in models/lm.py.

    When ``cfg.attn_impl`` resolves to "pallas" the write + attend run as
    ONE Pallas kernel over the head-major page pool
    (``ragged_paged_prefill_attention``): the chunk's real K/V are fused
    into the page walk and pages past ``lengths + n_real`` are skipped,
    so chunk cost tracks live tokens instead of pool width.  The lax
    fallback (explicit ``attn_impl="xla"``, or auto off-TPU) keeps the
    scatter + full-view gather + dense SDPA.

    Int8 pools (``kv`` the 4-tuple): the post-write scales are planned
    host-of-kernel in ``_chunk_page_scales`` (no page reads needed),
    then the write-window pages requantize-and-merge — in-kernel for
    the Pallas path (old/new scale arrays scalar-prefetched, fresh
    rows quantized before the one-hot merge, attend on the dequantized
    merged tile), in XLA for the fallback — and the attend runs over
    the dequantized view.  Same math both paths.

    Returns (y (b, c, d), kv') with kv' the same arity as ``kv``.
    """
    nh, nkv, hd, rot = _attn_dims(cfg)
    b, c, _ = u.shape
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    quant = len(kv) == 4
    if quant:
        k_pages, v_pages, k_scale, v_scale = kv
    else:
        k_pages, v_pages = kv
        k_scale = v_scale = None
    pg = cfg.kv_page_tokens
    W = page_table.shape[1]

    with jax.named_scope(scopes.ATTN_QKV):
        qkv = linear(params["wqkv"], u, compute_dtype)
        q, k, v = _split_qkv(qkv, cfg)
        if token_mask is None:
            real = jnp.ones((b, c), bool)
        else:
            real = token_mask > 0.5
        pad = c - jnp.sum(real.astype(jnp.int32), axis=1)          # (b,)
        pos = lengths[:, None] + jnp.arange(c)[None, :] - pad[:, None]
        posc = jnp.maximum(pos, 0)                                  # (b, c)
        if rot > 0:
            angles = rope_angles(posc, rot, cfg.rope_theta)
            q = apply_rope(q, angles)
            k = apply_rope(k, angles)

    if quant:
        ks_old = _layer_scales(k_scale, layer)
        vs_old = _layer_scales(v_scale, layer)
        ks_new, vs_new, takes = _chunk_page_scales(
            k, v, real, page_table, lengths, c - pad, ks_old, vs_old, pg
        )
        k_scale = jax.lax.dynamic_update_index_in_dim(
            k_scale, ks_new, layer, 0)
        v_scale = jax.lax.dynamic_update_index_in_dim(
            v_scale, vs_new, layer, 0)

    from mamba_distributed_tpu.ops.pallas.common import resolve_attn_impl

    if resolve_attn_impl(cfg.attn_impl) == "pallas":
        from mamba_distributed_tpu.ops.pallas.attention_kernels import (
            ragged_paged_prefill_attention,
        )

        # the kernel writes the chunk's K/V into the pages itself
        with jax.named_scope(scopes.ATTN_KERNEL):
            out, k_pages, v_pages = ragged_paged_prefill_attention(
                q, k, v, k_pages, v_pages, layer, page_table, lengths,
                c - pad,
                **({} if not quant else dict(
                    k_scale_old=ks_old, v_scale_old=vs_old,
                    k_scale_new=ks_new, v_scale_new=vs_new,
                )),
            )
    elif quant:
        from mamba_distributed_tpu.ops.quant import kv_quantize, kv_requant

        # the chunk's write WINDOW — the only pages that requantize or
        # write back — spans at most ceil(c/pg)+1 logical pages starting
        # at lengths//pg, so the merge gathers/scatters O(chunk) pages
        # per row, never O(table width) (the same live-traffic rule the
        # bf16 fallback keeps via gather_kv_pages(live_pages=))
        Wc = min(W, -(-c // pg) + 1)
        j0 = lengths // pg                              # (b,)
        wj = j0[:, None] + jnp.arange(Wc)[None, :]      # (b, Wc) logical
        in_range = wj < W
        wjc = jnp.where(in_range, wj, W - 1)
        wtbl = jnp.take_along_axis(page_table, wjc, axis=1)
        takes_w = jnp.take_along_axis(takes, wjc, axis=1) & in_range
        has_prior = (lengths[:, None] > wj * pg) & in_range
        # window-local chunk-token coordinates (real tokens only: posc
        # >= lengths >= j0*pg and posc < lengths + c <= (j0+Wc)*pg)
        lpos = jnp.clip(posc - (j0 * pg)[:, None], 0, Wc * pg - 1)
        lpidx = lpos // pg                              # (b, c)
        dst = jnp.where(takes_w, wtbl, 0)

        def merge(pages, old_scales, new_scales, x):
            # requantize window pages under their new scales, then
            # scatter the chunk's quantized rows into the flat view
            old_q = pages[layer, wtbl]                # (b, Wc, nkv, pg, hd)
            old_s = old_scales[wtbl]                  # (b, Wc, nkv)
            new_s = new_scales[wtbl]
            ratio = jnp.where(has_prior[..., None], old_s / new_s, 0.0)
            req = kv_requant(old_q, ratio[..., None, None])
            row_s = jnp.take_along_axis(new_s, lpidx[:, :, None], axis=1)
            q_rows = kv_quantize(x, row_s[..., None])  # (b, c, nkv, hd)
            view = jnp.moveaxis(req, 3, 2).reshape(b, Wc * pg, nkv, hd)
            view = jnp.concatenate(                    # pad slot for pads
                [view, jnp.zeros((b, 1, nkv, hd), view.dtype)], axis=1)
            idx = jnp.where(real, lpos, Wc * pg)
            view = view.at[jnp.arange(b)[:, None], idx].set(q_rows)
            merged = jnp.moveaxis(
                view[:, :-1].reshape(b, Wc, pg, nkv, hd), 2, 3
            )
            return pages.at[layer, dst].set(merged.astype(pages.dtype))

        with jax.named_scope(scopes.KV_WRITE):
            k_pages = merge(k_pages, ks_old, ks_new, k)
            v_pages = merge(v_pages, vs_old, vs_new, v)
        tokens = jnp.minimum(lengths + (c - pad), W * pg)
        with jax.named_scope(scopes.ATTN_KERNEL):
            kk, vv = gather_kv_pages(
                k_pages, v_pages, page_table,
                jnp.maximum((tokens + pg - 1) // pg, 1),
                k_scale=ks_new, v_scale=vs_new, dtype=compute_dtype,
                layer=layer,
            )
            out = _sdpa_positions(
                q, kk, vv, jnp.minimum(posc, W * pg - 1))
    else:
        pidx = jnp.clip(posc // pg, 0, W - 1)
        phys = jnp.where(
            real, jnp.take_along_axis(page_table, pidx, axis=1), 0
        )
        off = jnp.where(real, posc % pg, 0)
        # head-major pages: the (b, c) phys/off pair scatters
        # (b, c, nkv, hd) blocks one axis past the heads
        with jax.named_scope(scopes.KV_WRITE):
            k_pages = k_pages.at[layer, phys, :, off].set(
                k.astype(k_pages.dtype))
            v_pages = v_pages.at[layer, phys, :, off].set(
                v.astype(v_pages.dtype))
        # live extent after this chunk's write = prefix + its real
        # tokens; pages past it gather as trash (fully masked), so the
        # chunk's fallback cost tracks live tokens, not table width
        # (at least one page: a degenerate all-pad row clamps its
        # queries to position 0, which must stay a real gather)
        tokens = jnp.minimum(lengths + (c - pad), W * pg)
        with jax.named_scope(scopes.ATTN_KERNEL):
            kk, vv = gather_kv_pages(
                k_pages, v_pages, page_table,
                jnp.maximum((tokens + pg - 1) // pg, 1), layer=layer,
            )
            out = _sdpa_positions(
                q, kk, vv, jnp.minimum(posc, W * pg - 1))
    with jax.named_scope(scopes.ATTN_OUT):
        y = linear(
            params["out_proj"], out.reshape(b, c, nh * hd), compute_dtype
        )
    if quant:
        return y, (k_pages, v_pages, k_scale, v_scale)
    return y, (k_pages, v_pages)
