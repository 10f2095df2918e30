"""Pallas flash-attention kernel (causal, GQA) for the hybrid layers.

TPU-native counterpart of the flash-attn CUDA kernels the reference's
attention surface sits on one dep down (``mamba_ssm.modules.mha.MHA`` →
``flash_attn`` — mamba-ssm 2.2.2; the reference never enables attention,
SURVEY.md §2.3, but BASELINE config 5 requires it).  Re-derived for the
MXU/VMEM model, not translated:

  * grid = (batch, q-head, q-block, kv-block); the kv-block dimension is
    the sequential one — the online-softmax accumulator (running max,
    denominator, output) lives in VMEM scratch and streams KV through a
    bounded working set, exactly the flash construction;
  * fully-future (q-block, kv-block) pairs are *skipped* via ``pl.when``
    on the grid indices — unlike the XLA blockwise path
    (ops/blockwise_attention.py) whose branch-free schedule computes and
    masks them, the kernel recovers the ~2x causal FLOPs;
  * GQA routes the shared KV head via BlockSpec index maps
    (``hi // rep``) — Q heads never see repeated KV in HBM;
  * softmax statistics are carried per q-row in fp32; the row
    log-sum-exp is emitted in a lane-degenerate ``(..., tq, 8)`` layout
    (block spans the full trailing dim, so Mosaic tiling stays legal
    without transposing row statistics into lanes).

The backward is Pallas too (the flash-attn backward's trade): p is
recomputed per (q, kv) block pair from q/k and the saved row-lse — no
(t, t) tensor is ever materialized — with one kernel accumulating dq
over the sequential kv dimension and a second accumulating dk/dv over
the sequential q dimension; per-q-head dk/dv partials are group-summed
in XLA (same pattern as the SSD backward's dB/dC).  Gradient parity vs
the XLA blockwise path is pinned by tests/test_attention_pallas.py.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mamba_distributed_tpu.obs import scopes
from mamba_distributed_tpu.ops.pallas.common import resolve_interpret

_NEG_INF = float("-inf")


def _pick_block(t: int, target: int) -> int:
    """Block size for a (padded) sequence length: target, or all of t."""
    if t >= target:
        return target
    return -(-t // 8) * 8  # round up to the 8-sublane granule


def _causal_mask(qb, kb, q0, k0, tk_valid):
    """(qb, kb) bool: query row q0+i may attend key col k0+j (< tk_valid)."""
    qpos = jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 0) + q0
    kpos = jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 1) + k0
    return (qpos >= kpos) & (kpos < tk_valid)


def _fa_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, den_scr, acc_scr,
    *, nk: int, sm_scale: float, offset: int, tk_valid: int,
):
    """One (batch, q-head, q-block, kv-block) cell of the forward."""
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    qb = q_ref.shape[2]
    kb = k_ref.shape[2]

    @pl.when(kj == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        den_scr[...] = jnp.zeros_like(den_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # skip fully-future blocks: first key of this block vs last query row
    @pl.when(kj * kb <= qi * qb + qb - 1 + offset)
    def _():
        q = q_ref[0, 0]                                  # (qb, hd)
        s = jax.lax.dot_general(                         # (qb, kb) fp32
            q, k_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        mask = _causal_mask(qb, kb, qi * qb + offset, kj * kb, tk_valid)
        s = jnp.where(mask, s, _NEG_INF)

        # lanes of the stat scratches hold replicated copies; a lane-max
        # read avoids ref lane-slicing (no Mosaic sub-128 memref slices)
        m_prev = jnp.max(m_scr[...], axis=1, keepdims=True)   # (qb, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # rows with every key masked so far keep m = -inf; guard both exps
        # (values are finite or -inf, never NaN/+inf, so `> -inf` stands in
        # for isfinite — which this jax's Mosaic lowering lacks)
        scale = jnp.where(m_prev > _NEG_INF, jnp.exp(m_prev - m_new), 0.0)
        p = jnp.where(s > _NEG_INF, jnp.exp(s - m_new), 0.0)      # (qb, kb)

        acc_scr[...] = acc_scr[...] * scale + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        den_scr[...] = den_scr[...] * scale + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(kj == nk - 1)
    def _():
        den = jnp.max(den_scr[...], axis=1, keepdims=True)    # (qb, 1)
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(den, 1e-30)).astype(
            o_ref.dtype
        )
        # row lse; rows that saw no unmasked key (possible only for
        # offset < 0 uses) get +inf so the backward's exp(s - lse) is 0
        # there.  Padded query rows attend normally and get a finite lse —
        # their backward is harmless because their dO rows are zero.
        m_fin = jnp.max(m_scr[...], axis=1, keepdims=True)
        lse = jnp.where(
            den > 0.0, m_fin + jnp.log(jnp.maximum(den, 1e-30)),
            jnp.inf,
        )
        lse_ref[0, 0] = jnp.broadcast_to(lse, (lse.shape[0], 8))


def _fa_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, dq_ref, dq_scr,
    *, nk: int, sm_scale: float, offset: int, tk_valid: int,
):
    """dq for one q-block, accumulated over the sequential kv dimension."""
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    qb = q_ref.shape[2]
    kb = k_ref.shape[2]

    @pl.when(kj == 0)
    def _():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(kj * kb <= qi * qb + qb - 1 + offset)
    def _():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        mask = _causal_mask(qb, kb, qi * qb + offset, kj * kb, tk_valid)
        s = jnp.where(mask, s, _NEG_INF)
        # stat blocks carry lane-replicated values; lane-max reads avoid
        # sub-128 vector lane slices (Mosaic-safe)
        lse = jnp.max(lse_ref[0, 0], axis=1, keepdims=True)   # (qb, 1)
        p = jnp.exp(s - lse)                             # (qb, kb)
        dp = jax.lax.dot_general(                        # dO @ V^T
            do_ref[0, 0], v_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dlt = jnp.max(dlt_ref[0, 0], axis=1, keepdims=True)
        ds = p * (dp - dlt)
        dq_scr[...] = dq_scr[...] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale

    @pl.when(kj == nk - 1)
    def _():
        dq_ref[0, 0] = dq_scr[...]


def _fa_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, dk_ref, dv_ref,
    dk_scr, dv_scr, *, nq: int, sm_scale: float, offset: int, tk_valid: int,
):
    """Per-q-head dk/dv partials for one kv-block, over the sequential
    q dimension (group-summed over GQA reps in XLA afterwards)."""
    kj = pl.program_id(2)
    qi = pl.program_id(3)
    qb = q_ref.shape[2]
    kb = k_ref.shape[2]

    @pl.when(qi == 0)
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(kj * kb <= qi * qb + qb - 1 + offset)
    def _():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        mask = _causal_mask(qb, kb, qi * qb + offset, kj * kb, tk_valid)
        s = jnp.where(mask, s, _NEG_INF)
        # stat blocks carry lane-replicated values; lane-max reads avoid
        # sub-128 vector lane slices (Mosaic-safe)
        lse = jnp.max(lse_ref[0, 0], axis=1, keepdims=True)   # (qb, 1)
        p = jnp.exp(s - lse)                             # (qb, kb)
        do = do_ref[0, 0]
        # dV += P^T @ dO   (contract the q/sublane dim of both)
        dv_scr[...] = dv_scr[...] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dlt = jnp.max(dlt_ref[0, 0], axis=1, keepdims=True)
        ds = p * (dp - dlt)
        # dK += dS^T @ Q
        dk_scr[...] = dk_scr[...] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[0, 0] = dk_scr[...]
        dv_ref[0, 0] = dv_scr[...]


def _fa_fwd_impl(qt, kt, vt, offset, tk_valid, qb, kb, interpret):
    """(b, nh, tq, hd), (b, nkv, tk, hd) -> o (b, nh, tq, hd), lse."""
    b, nh, tq, hd = qt.shape
    nkv, tk = kt.shape[1], kt.shape[2]
    rep = nh // nkv
    nq, nk = tq // qb, tk // kb
    sm_scale = 1.0 / math.sqrt(hd)
    grid = (b, nh, nq, nk)

    q_spec = pl.BlockSpec((1, 1, qb, hd), lambda bi, hi, qi, kj: (bi, hi, qi, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, kb, hd), lambda bi, hi, qi, kj: (bi, hi // rep, kj, 0)
    )
    lse_spec = pl.BlockSpec((1, 1, qb, 8), lambda bi, hi, qi, kj: (bi, hi, qi, 0))

    o, lse = pl.pallas_call(
        functools.partial(
            _fa_fwd_kernel, nk=nk, sm_scale=sm_scale, offset=offset,
            tk_valid=tk_valid,
        ),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, nh, tq, hd), qt.dtype),
            jax.ShapeDtypeStruct((b, nh, tq, 8), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((qb, 128), jnp.float32),
            pltpu.VMEM((qb, 128), jnp.float32),
            pltpu.VMEM((qb, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
        name="fa_fwd",
    )(qt, kt, vt)
    return o, lse


def _fa_bwd_dq_call(qt, kt, vt, do, lse, dlt, offset, tk_valid, qb, kb,
                    interpret):
    """Pair-level dq (b, nh, tq, hd) fp32 given row lse/delta in the
    lane-degenerate (..., 8) layout.  Reused per ring-attention hop."""
    b, nh, tq, hd = qt.shape
    nkv, tk = kt.shape[1], kt.shape[2]
    rep = nh // nkv
    nq, nk = tq // qb, tk // kb
    sm_scale = 1.0 / math.sqrt(hd)

    q_spec = pl.BlockSpec((1, 1, qb, hd), lambda bi, hi, qi, kj: (bi, hi, qi, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, kb, hd), lambda bi, hi, qi, kj: (bi, hi // rep, kj, 0)
    )
    lse_spec = pl.BlockSpec((1, 1, qb, 8), lambda bi, hi, qi, kj: (bi, hi, qi, 0))
    seq_kv = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    )

    return pl.pallas_call(
        functools.partial(
            _fa_bwd_dq_kernel, nk=nk, sm_scale=sm_scale, offset=offset,
            tk_valid=tk_valid,
        ),
        grid=(b, nh, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, lse_spec, lse_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, nh, tq, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((qb, hd), jnp.float32)],
        compiler_params=seq_kv,
        interpret=interpret,
        name="fa_bwd_dq",
    )(qt, kt, vt, do, lse, dlt)


def _fa_bwd_dkv_call(qt, kt, vt, do, lse, dlt, offset, tk_valid, qb, kb,
                     interpret):
    """Pair-level (dk, dv) (b, nkv, tk, hd) fp32, GQA group-summed."""
    b, nh, tq, hd = qt.shape
    nkv, tk = kt.shape[1], kt.shape[2]
    rep = nh // nkv
    nq, nk = tq // qb, tk // kb
    sm_scale = 1.0 / math.sqrt(hd)
    seq_kv = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    )

    # grid loops kv blocks in the third slot, q blocks sequential
    rq_spec = pl.BlockSpec((1, 1, qb, hd), lambda bi, hi, kj, qi: (bi, hi, qi, 0))
    rkv_spec = pl.BlockSpec(
        (1, 1, kb, hd), lambda bi, hi, kj, qi: (bi, hi // rep, kj, 0)
    )
    rkv_out = pl.BlockSpec((1, 1, kb, hd), lambda bi, hi, kj, qi: (bi, hi, kj, 0))
    rlse_spec = pl.BlockSpec((1, 1, qb, 8), lambda bi, hi, kj, qi: (bi, hi, qi, 0))
    dk_part, dv_part = pl.pallas_call(
        functools.partial(
            _fa_bwd_dkv_kernel, nq=nq, sm_scale=sm_scale, offset=offset,
            tk_valid=tk_valid,
        ),
        grid=(b, nh, nk, nq),
        in_specs=[rq_spec, rkv_spec, rkv_spec, rq_spec, rlse_spec, rlse_spec],
        out_specs=[rkv_out, rkv_out],
        out_shape=[
            jax.ShapeDtypeStruct((b, nh, tk, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, nh, tk, hd), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((kb, hd), jnp.float32),
            pltpu.VMEM((kb, hd), jnp.float32),
        ],
        compiler_params=seq_kv,
        interpret=interpret,
        name="fa_bwd_dkv",
    )(qt, kt, vt, do, lse, dlt)

    # GQA group-sum of the per-q-head partials (rep == 1 is a no-op reshape)
    dk = jnp.sum(dk_part.reshape(b, nkv, rep, tk, hd), axis=2)
    dv = jnp.sum(dv_part.reshape(b, nkv, rep, tk, hd), axis=2)
    return dk, dv


def lane8(x):
    """(..., t) row statistic -> the kernels' lane-degenerate (..., t, 8)."""
    return jnp.broadcast_to(x[..., None], (*x.shape, 8))


def _fa_bwd_impl(qt, kt, vt, o, lse, do, offset, tk_valid, qb, kb, interpret):
    # D_i = rowsum(dO ⊙ O), emitted in the same lane-degenerate layout as
    # lse (elementwise + lane reduction: XLA fuses it)
    dlt = lane8(jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
    ))
    dq = _fa_bwd_dq_call(qt, kt, vt, do, lse, dlt, offset, tk_valid, qb, kb,
                         interpret)
    dk, dv = _fa_bwd_dkv_call(qt, kt, vt, do, lse, dlt, offset, tk_valid,
                              qb, kb, interpret)
    return dq, dk, dv


def flash_pair_fwd(qt, kt, vt, offset, qb=256, kb=256, interpret=None):
    """Raw pair forward: (o (b, nh, tq, hd), lse (b, nh, tq) fp32).

    Head-major layouts, NOT differentiable on its own — ring attention
    (parallel/ring_attention.py) composes these pair calls under its own
    custom_vjp, merging per-hop (o, lse) partials and reusing
    ``flash_pair_dq``/``flash_pair_dkv`` with the GLOBAL lse in the
    backward (the flash decomposition is exact per (q, kv) pair given
    the merged lse and delta).  ``offset`` must be static: ring hops are
    fully-past (offset = tq), diagonal (0), or skipped.
    """
    interpret = resolve_interpret(interpret)
    tq, tk = qt.shape[2], kt.shape[2]
    qb = _pick_block(tq, qb)
    kb = _pick_block(tk, kb)
    pad_q, pad_k = -tq % qb, -tk % kb
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    o, lse8 = _fa_fwd_impl(qt, kt, vt, int(offset), tk, qb, kb, interpret)
    return o[:, :, :tq], lse8[:, :, :tq, 0]


def flash_pair_dq(qt, kt, vt, do, lse, dlt, offset, qb=256, kb=256,
                  interpret=None):
    """Raw pair dq (fp32) from the GLOBAL row lse / delta (b, nh, tq)."""
    interpret = resolve_interpret(interpret)
    tq, tk = qt.shape[2], kt.shape[2]
    qb = _pick_block(tq, qb)
    kb = _pick_block(tk, kb)
    pad_q, pad_k = -tq % qb, -tk % kb
    pads = ((0, 0), (0, 0), (0, pad_q), (0, 0))
    if pad_q:
        qt, do = jnp.pad(qt, pads), jnp.pad(do, pads)
        lse = jnp.pad(lse, ((0, 0), (0, 0), (0, pad_q)),
                      constant_values=jnp.inf)
        dlt = jnp.pad(dlt, ((0, 0), (0, 0), (0, pad_q)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    dq = _fa_bwd_dq_call(qt, kt, vt, do, lane8(lse), lane8(dlt),
                         int(offset), tk, qb, kb, interpret)
    return dq[:, :, :tq]


def flash_pair_dkv(qt, kt, vt, do, lse, dlt, offset, qb=256, kb=256,
                   interpret=None):
    """Raw pair (dk, dv) (fp32, GQA group-summed) from GLOBAL lse/delta."""
    interpret = resolve_interpret(interpret)
    tq, tk = qt.shape[2], kt.shape[2]
    qb = _pick_block(tq, qb)
    kb = _pick_block(tk, kb)
    pad_q, pad_k = -tq % qb, -tk % kb
    pads = ((0, 0), (0, 0), (0, pad_q), (0, 0))
    if pad_q:
        qt, do = jnp.pad(qt, pads), jnp.pad(do, pads)
        lse = jnp.pad(lse, ((0, 0), (0, 0), (0, pad_q)),
                      constant_values=jnp.inf)
        dlt = jnp.pad(dlt, ((0, 0), (0, 0), (0, pad_q)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    dk, dv = _fa_bwd_dkv_call(qt, kt, vt, do, lane8(lse), lane8(dlt),
                              int(offset), tk, qb, kb, interpret)
    return dk[:, :, :tk], dv[:, :, :tk]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fa_core(qt, kt, vt, offset, tk_valid, qb, kb, interpret):
    o, _ = _fa_fwd_impl(qt, kt, vt, offset, tk_valid, qb, kb, interpret)
    return o


def _fa_core_fwd(qt, kt, vt, offset, tk_valid, qb, kb, interpret):
    o, lse = _fa_fwd_impl(qt, kt, vt, offset, tk_valid, qb, kb, interpret)
    return o, (qt, kt, vt, o, lse)


@jax.named_scope(scopes.ATTN_KERNEL)  # a custom_vjp's backward has no name
def _fa_core_bwd(offset, tk_valid, qb, kb, interpret, res, do):
    qt, kt, vt, o, lse = res
    dq, dk, dv = _fa_bwd_impl(
        qt, kt, vt, o, lse, do, offset, tk_valid, qb, kb, interpret
    )
    return (
        dq.astype(qt.dtype), dk.astype(kt.dtype), dv.astype(vt.dtype)
    )


_fa_core.defvjp(_fa_core_fwd, _fa_core_bwd)


def flash_sdpa_causal(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    offset: int = 0,
    q_block: int = 256,
    k_block: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Causal softmax(QK^T/sqrt(d))V with GQA broadcast — Pallas flash.

    Same contract as ops/blockwise_attention.blockwise_sdpa_causal:
    q (b, tq, nh, hd); k/v (b, tk, nkv, hd); ``offset`` = absolute
    position of q[0] minus that of k[0] (static).  Sequence lengths are
    padded to block multiples (padded keys are masked via the key-length
    term; padded query rows are computed then sliced off — their
    cotangent rows are zero through the pad/slice pair, so ds vanishes
    on them and the backward stays NaN-free), head dims pass through
    whole (blocks span the full trailing dim).  ``interpret=None``
    auto-selects the Pallas interpreter off-TPU.
    """
    interpret = resolve_interpret(interpret)
    b, tq, nh, hd = q.shape
    tk, nkv = k.shape[1], k.shape[2]
    if nh % nkv:
        raise ValueError(f"num_heads {nh} not a multiple of kv heads {nkv}")
    offset = int(offset)

    qb = _pick_block(tq, q_block)
    kb = _pick_block(tk, k_block)
    pad_q = -tq % qb
    pad_k = -tk % kb

    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))

    o = _fa_core(qt, kt, vt, offset, tk, qb, kb, interpret)
    if pad_q:
        o = o[:, :, :tq]
    return jnp.moveaxis(o, 1, 2)


# ---------------------------------------------------------------------------
# Ragged paged decode attention ("Ragged Paged Attention: A High-Performance
# and Flexible LLM Inference Kernel for TPU", PAPERS.md).
#
# Serving decode over the paged KV pool (models/attention.py): each row of
# the slot batch sits at its OWN position, its KV scattered across pool
# pages named by its page-table row.  The kernel walks a lane's page list
# in BLOCKS of B pages, the table, lengths and layer index
# scalar-prefetched, so no (S, W*page) gather ever exists:
#
#   * grid (lanes, ceil(W / B)), blocks sequential: one cell holds every
#     KV head of B pages, not one head of one page;
#   * a page is ONE copy: its nkv heads lie side by side in the pool
#     ((A, P, nkv, pg, hd), head-major inside a page), so the pool is
#     handed over B times for K and B times for V (the same buffer: an
#     operand is a window, not a copy) and window i of a cell is the
#     (nkv, pg, hd) page ``pool[layer, tbl[s, j*B + i]]``, fetched by the
#     pipeline while the cell before computes, across lane boundaries too;
#   * B from shapes (``_pick_page_block``): B * pg = 512 tokens inside a
#     VMEM budget, never more than the table is wide.  A head's scores,
#     softmax update and value product run on the whole (R8, B*pg) block;
#   * a dead page moves nothing: past its last live block a window
#     repeats the page it showed there, and the pipeline does not fetch a
#     block whose index stands still; the dead cell's body is skipped,
#     and positions past kv_len inside the last live block are masked by
#     the iota.  A lane with kv_len == 0 computes nothing and emits zeros;
#   * which page a window shows is worked out ONCE, outside the kernel
#     (``_window_pages``: an (S, W)-sized integer op), and prefetched in
#     the table's place: an index map is one SMEM read.  A cell pays for
#     each of its 2B windows whether or not anything moves, so what an
#     index map costs is paid S * W * 2 times a call.
#
# What this replaced (PR 36): a grid (S, nkv, W) of one (pg, hd) tile a
# cell: 8,192 cells a call at the benchmark's 16 lanes x 4 heads x 128
# table entries, 1.53 ms a call at either head width, all of it per-cell
# overhead.  docs/KERNELS.md has the measurements, and those of the route
# not kept (the pool left in HBM and copied by the kernel itself).
# ---------------------------------------------------------------------------

# python-side-effect trace counters (one bump per jit trace): the whole
# point of the fixed (S, W) layout is that occupancy/length changes never
# retrace — tests/test_paged_attention.py pins both.
TRACE_COUNTS = {"ragged_decode": 0, "ragged_prefill": 0}

# the decode walk's block: tokens a block, and the VMEM its 2B page
# windows may take, double-buffered.  512 tokens make a (R8, 512) score
# block a head; at the benchmark's pages (4 heads x 64 tokens x 128 lanes
# of bf16 = 64 KB) that is B = 8 and 2 MB, far inside the 16 MB a v5e
# kernel gets by default.
_RPA_BLOCK_TOKENS = 512
_RPA_VMEM_BYTES = 8 * 2**20


def _pick_page_block(W: int, nkv: int, pg: int, hd: int, itemsize: int) -> int:
    """Pages a block of the decode walk, from shapes alone: 512 tokens'
    worth, as many as the VMEM budget holds double-buffered for K and V
    (a row pads to 128 lanes in VMEM), at least one and never more than
    the table's width (a narrower table is one block).  B need not
    divide W: the last block's missing pages are dead pages."""
    page_bytes = nkv * pg * (-(-hd // 128) * 128) * itemsize
    b = min(_RPA_BLOCK_TOKENS // pg, _RPA_VMEM_BYTES // (4 * page_bytes), W)
    return max(1, b)


def _window_pages(page_table, kv_len, pg: int, bp: int) -> jax.Array:
    """(S, nb * bp) int32: the physical page window ``i`` of block ``j``
    of lane ``s`` shows, at ``[s, j * bp + i]`` (``kv_len`` at most the
    table's W * pg): page ``j * bp + i`` of the lane while that page is
    live, and after it the page the window
    showed in its last live block (entry ``i`` of the table if it never
    had one), so that a dead window's index stands still from cell to
    cell and nothing is fetched for it."""
    W = page_table.shape[1]
    w = jnp.arange(-(-W // bp) * bp, dtype=jnp.int32)[None]
    j, i = w // bp, w % bp
    last = ((kv_len + (pg - 1)) // pg - 1)[:, None]    # a lane's last live page
    j = jnp.minimum(j, jnp.maximum(last - i, 0) // bp)
    return jnp.take_along_axis(
        page_table, jnp.minimum(j * bp + i, W - 1), axis=1)


def _layer_operand(layer) -> jax.Array:
    """The pool's layer index as the (1,) int32 scalar-prefetch operand
    both ragged kernels' K/V index maps read."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


def _rpa_kernel(
    layer_ref, win_ref, len_ref, *rest,
    pg: int, bp: int, sm_scale: float, quant: bool = False,
):
    """One (lane, block of ``bp`` pages) cell of the ragged decode
    forward, every KV head in it.

    ``layer_ref`` (the pool's layer index) and ``win_ref`` (the windows'
    pages, ``_window_pages``) are read by the index maps: the ``bp`` K
    windows and ``bp`` V windows arrive as that layer's (1, nkv, pg, hd)
    pages.

    ``quant`` (int8 page pools): two extra scalar-prefetched (P, nkv)
    f32 scale arrays ride between the metadata and the tensor refs; the
    block is read as int8 and dequantized IN-REGISTER — each page's K
    scale folds into its columns of the score block, its V scale into
    its columns of the probabilities — one scalar per (page, head), no
    dequantized page ever materializes in VMEM.
    """
    if quant:
        ks_ref, vs_ref, *rest = rest
    q_ref, *rest = rest
    k_refs, v_refs = rest[:bp], rest[bp:2 * bp]
    o_ref, m_scr, den_scr, acc_scr = rest[2 * bp:]
    s = pl.program_id(0)
    j = pl.program_id(1)
    nkv, r8 = q_ref.shape[1], q_ref.shape[2]
    bt = bp * pg                                  # tokens a block

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        den_scr[...] = jnp.zeros_like(den_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kv_len = len_ref[s]

    # whole blocks at/past the row's length are SKIPPED, not masked —
    # the ragged saving (a dead row, kv_len == 0, skips everything)
    @pl.when(j * bt < kv_len)
    def _():
        kpos = jax.lax.broadcasted_iota(jnp.int32, (r8, bt), 1) + j * bt
        alive = kpos < kv_len
        if quant:
            # per-(page, head) scales as one (1, bt) row a head: page i's
            # scalar on its pg columns (a dead page's columns are masked)
            col = jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
            phys = [win_ref[s, j * bp + i] for i in range(bp)]

            def scale_row(ref, h):
                row = jnp.zeros((1, bt), jnp.float32)
                for i in range(bp):
                    row = jnp.where(col >= i * pg, ref[phys[i], h], row)
                return row

        for h in range(nkv):
            q = q_ref[0, h]                              # (R8, hd)
            k = jnp.concatenate([r[0, h] for r in k_refs], axis=0)
            v = jnp.concatenate([r[0, h] for r in v_refs], axis=0)
            if quant:
                # int8 block -> fp32 products; the scales come after
                q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
            scores = jax.lax.dot_general(                # (R8, bt) fp32
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sm_scale
            if quant:
                scores = scores * scale_row(ks_ref, h)
            scores = jnp.where(alive, scores, _NEG_INF)

            # lane-replicated row stats; lane-max reads (no sub-128 slices)
            m_prev = jnp.max(m_scr[h], axis=1, keepdims=True)
            m_new = jnp.maximum(
                m_prev, jnp.max(scores, axis=1, keepdims=True))
            scale = jnp.where(
                m_prev > _NEG_INF, jnp.exp(m_prev - m_new), 0.0)
            p = jnp.where(scores > _NEG_INF, jnp.exp(scores - m_new), 0.0)
            den_scr[h] = den_scr[h] * scale + jnp.sum(
                p, axis=1, keepdims=True)
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            pv = jax.lax.dot_general(                    # (R8, hd) fp32
                p * scale_row(vs_ref, h) if quant else p.astype(v.dtype),
                v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc_scr[h] = acc_scr[h] * scale + pv

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        den = jnp.max(den_scr[...], axis=2, keepdims=True)
        # rows with no live page (kv_len == 0) emit zeros, not NaN
        o_ref[0] = (acc_scr[...] / jnp.maximum(den, 1e-30)).astype(
            o_ref.dtype
        )


def ragged_paged_decode_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    layer,
    page_table: jax.Array,
    kv_len: jax.Array,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Paged decode attention with per-row lengths.

    q (S, nh, hd) — one query token per slot; k_pages/v_pages
    (A, P, nkv, page, hd) — the WHOLE head-major page pool, every
    attention layer's pages (page 0 of each layer = trash); ``layer``
    — which of the A layers to read, an int or a traced int32 scalar: it
    rides the scalar-prefetch block beside the windows' pages and the K/V
    index maps address ``(layer, tbl[s, j*B + i])``, so a caller's layer
    loop hands over the pool it carries and never slices it;
    page_table (S, W) int32; kv_len (S,) int32 — tokens readable
    per row (INCLUDING any token written this step).  Returns
    (S, nh, hd).

    ``k_scale``/``v_scale`` (int8 pools: THIS layer's (P, nkv) f32, one
    symmetric scale per (physical page, kv head)) ride the scalar-prefetch
    channel next to the page table, and the kernel dequantizes each
    visited int8 block in-register — a page's scalar folds into its
    columns of the scores (K) and of the probabilities (V), so page-walk
    HBM traffic is the int8 bytes and nothing widened ever round-trips.

    Numerics match the lax fallback (gather + masked SDPA,
    models/attention._sdpa_positions; int8: dequantizing gather) to fp
    tolerance; one jit trace covers every occupancy / length mix at a
    fixed (S, W) layout (``TRACE_COUNTS["ragged_decode"]``).
    ``interpret=None`` auto-selects the Pallas interpreter off-TPU.
    """
    interpret = resolve_interpret(interpret)
    TRACE_COUNTS["ragged_decode"] += 1
    quant = k_scale is not None
    S, nh, hd = q.shape
    _, P, nkv, pg, _ = k_pages.shape
    W = page_table.shape[1]
    if nh % nkv:
        raise ValueError(f"num_heads {nh} not a multiple of kv heads {nkv}")
    rep = nh // nkv
    # GQA rep as the sublane dim of each (slot, kv-head) tile, padded to
    # the 8-sublane granule; pad rows attend real keys and are sliced off
    R8 = -(-rep // 8) * 8
    qh = q.reshape(S, nkv, rep, hd)
    if R8 != rep:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, R8 - rep), (0, 0)))
    bp = _pick_page_block(W, nkv, pg, hd, k_pages.dtype.itemsize)

    # index maps take the grid ids plus EVERY scalar-prefetch operand
    # (3 plain, 5 with the int8 scales) — *pf absorbs the difference
    q_spec = pl.BlockSpec((1, nkv, R8, hd), lambda s, j, *pf: (s, 0, 0, 0))

    def page_window(i):
        # the pool is STORED head-major (A, P, nkv, pg, hd): a page is a
        # (1, nkv, pg, hd) block — Mosaic's last-two-dims tiling — under
        # a squeezed layer dimension, addressed straight off the layer
        # index and the window's page: no per-call slice or transpose of
        # the pool on the hot path
        return pl.BlockSpec(
            (None, 1, nkv, pg, hd),
            lambda s, j, lyr, win, *pf: (lyr[0], win[s, j * bp + i], 0, 0, 0),
        )

    windows = [page_window(i) for i in range(bp)]
    # a length past the table reads the table and no further
    kv_len = jnp.minimum(kv_len.astype(jnp.int32), W * pg)
    prefetch = (
        _layer_operand(layer),
        _window_pages(page_table.astype(jnp.int32), kv_len, pg, bp),
        kv_len,
    )
    if quant:
        prefetch += (k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32))
    out = pl.pallas_call(
        functools.partial(
            _rpa_kernel, pg=pg, bp=bp, sm_scale=1.0 / math.sqrt(hd),
            quant=quant,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(S, -(-W // bp)),
            in_specs=[q_spec] + windows + windows,
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((nkv, R8, 128), jnp.float32),
                pltpu.VMEM((nkv, R8, 128), jnp.float32),
                pltpu.VMEM((nkv, R8, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((S, nkv, R8, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="ragged_paged_decode_attention",
    )(*prefetch, qh, *([k_pages] * bp), *([v_pages] * bp))
    return out[:, :, :rep].reshape(S, nh, hd)


# ---------------------------------------------------------------------------
# Ragged paged PREFILL attention: one chunk of prompt ingestion against
# the head-major page pool, as one kernel.
#
# The chunked hybrid prefill (models/attention.attention_mixer_chunk) used
# to scatter the chunk's K/V into pages and then GATHER the row's entire
# page view for a dense masked SDPA — O(pool width) work per chunk no
# matter how few tokens were live.  This kernel is the prefill half of
# the ragged-paged construction: grid (rows, kv-heads, page-blocks) with
# the page dimension sequential, the page table scalar-prefetched (the
# BlockSpec index map picks each row's physical page, so no (b, W*page)
# view ever exists), and every page at/past ``lengths[r] + chunk_real[r]``
# skipped outright.  The chunk's K/V page WRITE is fused in: each visited
# page merges the chunk rows that land in it (an exact one-hot-select
# matmul — every output row is one input row or the old page row) before
# the attend, and the page-pool outputs alias the inputs so XLA updates
# the pool in place.  Cells whose page takes no chunk token flush their
# (unchanged or garbage) block to the trash page via the output index
# map — a real page is only ever written by the one cell that owns it.
# ---------------------------------------------------------------------------


def _rpp_kernel(
    layer_ref, tbl_ref, len_ref, creal_ref, *rest,
    nw: int, pg: int, c: int, rep: int, sm_scale: float,
    quant: bool = False,
):
    """One (row, kv-head, page) cell of the fused prefill forward.

    ``layer_ref`` (the pool's layer index) is read by the index maps
    alone, as in ``_rpa_kernel``.

    ``quant`` (int8 page pools): four extra scalar-prefetched (P, nkv)
    f32 scale arrays — OLD and NEW for K and V.  The NEW scales are
    planned outside (models/attention._chunk_page_scales — no page
    reads needed, so nothing extra streams through the kernel); the
    kernel re-expresses the old int8 rows under the new scale
    (``round(q_old * old/new)``), quantizes the chunk's fresh rows
    BEFORE the one-hot merge, flushes the merged int8 page, and attends
    on the dequantized merged tile (scale * int8, in-register).
    """
    if quant:
        (kso_ref, ksn_ref, vso_ref, vsn_ref, q_ref, kc_ref, vc_ref,
         kp_ref, vp_ref, o_ref, ko_ref, vo_ref, m_scr, den_scr,
         acc_scr) = rest
    else:
        (q_ref, kc_ref, vc_ref, kp_ref, vp_ref, o_ref, ko_ref, vo_ref,
         m_scr, den_scr, acc_scr) = rest
    r = pl.program_id(0)
    h = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        den_scr[...] = jnp.zeros_like(den_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ln = len_ref[r]                      # tokens cached before this chunk
    creal = creal_ref[r]                 # real (non-pad) chunk tokens
    total = ln + creal                   # readable extent after the write
    pad = c - creal                      # left-pad inside the chunk

    # ---- fused page write: merge the chunk rows landing in this page.
    # Page position t holds absolute kpos = j*pg + t and takes chunk row
    # i = kpos - ln + pad iff ln <= kpos < total; the (pg, c) one-hot
    # select contraction is exact (each output row is 1.0 * one chunk row)
    kc = kc_ref[0, 0]                                    # (C8, hd)
    vc = vc_ref[0, 0]
    C8 = kc.shape[0]
    tpos = jax.lax.broadcasted_iota(jnp.int32, (pg, C8), 0) + j * pg
    ci = jax.lax.broadcasted_iota(jnp.int32, (pg, C8), 1)
    sel = (
        (ci == tpos - ln + pad) & (tpos >= ln) & (tpos < total)
    ).astype(jnp.float32)
    k_rows = jax.lax.dot_general(                        # (pg, hd) fp32
        sel, kc.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    v_rows = jax.lax.dot_general(
        sel, vc.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    kpos_col = jax.lax.broadcasted_iota(jnp.int32, (pg, 1), 0) + j * pg
    written = (kpos_col >= ln) & (kpos_col < total)       # (pg, 1)
    if quant:
        from mamba_distributed_tpu.ops.quant import kv_quantize, kv_requant

        phys = tbl_ref[r, j]
        kso, ksn = kso_ref[phys, h], ksn_ref[phys, h]
        vso, vsn = vso_ref[phys, h], vsn_ref[phys, h]
        has_prior = ln > j * pg
        # old rows re-express under the (possibly grown) new scale; a
        # page with NO prior content of this sequence ignores its stale
        # scale outright (recycled-page garbage can't leak in).  The
        # round/clip math is the SHARED ops/quant helpers — the same
        # functions the lax fallback and the decode-step write call —
        # so the two paths can never disagree on a stored value.
        ratio_k = jnp.where(has_prior, kso / ksn, 0.0)
        ratio_v = jnp.where(has_prior, vso / vsn, 0.0)
        merged_k_q = jnp.where(
            written, kv_quantize(k_rows, ksn), kv_requant(kp_ref[0, 0],
                                                          ratio_k))
        merged_v_q = jnp.where(
            written, kv_quantize(v_rows, vsn), kv_requant(vp_ref[0, 0],
                                                          ratio_v))
        ko_ref[0, 0] = merged_k_q.astype(ko_ref.dtype)
        vo_ref[0, 0] = merged_v_q.astype(vo_ref.dtype)
        # attend on what storage now holds: dequantized requantized rows
        merged_k = merged_k_q * ksn                       # (pg, hd) fp32
        merged_v = merged_v_q * vsn
    else:
        merged_k = jnp.where(
            written, k_rows.astype(kp_ref.dtype), kp_ref[0, 0]
        )
        merged_v = jnp.where(
            written, v_rows.astype(vp_ref.dtype), vp_ref[0, 0]
        )
        # every cell writes its out block (an unwritten block would
        # flush undefined VMEM); the out index map sends no-write cells
        # to trash
        ko_ref[0, 0] = merged_k
        vo_ref[0, 0] = merged_v

    # ---- attend: whole pages at/past the row's post-write extent are
    # SKIPPED — chunk cost tracks live tokens (an all-pad row skips all)
    @pl.when(j * pg < total)
    def _():
        q = q_ref[0, 0]                                  # (Q8, hd)
        if quant:
            q = q.astype(jnp.float32)  # merged tile is dequantized fp32
        scores = jax.lax.dot_general(                    # (Q8, pg) fp32
            q, merged_k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        # sublane s is (chunk idx i = s // rep, GQA rep e = s % rep);
        # query i sits at absolute position ln + i - pad (pad queries
        # clamp to 0 — garbage that dies with its discarded positions)
        qi = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0) // rep
        qpos = jnp.maximum(ln + qi - pad, 0)
        kpos = jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1
        ) + j * pg
        mask = (kpos <= qpos) & (kpos < total)
        scores = jnp.where(mask, scores, _NEG_INF)

        # lane-replicated row stats; lane-max reads (no sub-128 slices)
        m_prev = jnp.max(m_scr[...], axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        scale = jnp.where(m_prev > _NEG_INF, jnp.exp(m_prev - m_new), 0.0)
        p = jnp.where(scores > _NEG_INF, jnp.exp(scores - m_new), 0.0)

        acc_scr[...] = acc_scr[...] * scale + jax.lax.dot_general(
            p.astype(merged_v.dtype), merged_v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        den_scr[...] = den_scr[...] * scale + jnp.sum(
            p, axis=1, keepdims=True
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(j == nw - 1)
    def _():
        den = jnp.max(den_scr[...], axis=1, keepdims=True)
        # rows with nothing readable (empty chunk on an empty cache)
        # emit zeros, not NaN
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(den, 1e-30)).astype(
            o_ref.dtype
        )


def ragged_paged_prefill_attention(
    q: jax.Array,
    k_chunk: jax.Array,
    v_chunk: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    layer,
    page_table: jax.Array,
    lengths: jax.Array,
    chunk_real: jax.Array,
    k_scale_old: jax.Array | None = None,
    v_scale_old: jax.Array | None = None,
    k_scale_new: jax.Array | None = None,
    v_scale_new: jax.Array | None = None,
    interpret: bool | None = None,
):
    """Fused paged prefill: write one chunk's K/V into each row's pages,
    then attend every chunk query over the page view.

    q (b, c, nh, hd) — RoPE'd chunk queries; k_chunk/v_chunk
    (b, c, nkv, hd) — the chunk's RoPE'd K/V (left-pad prefix rows are
    ignored); k_pages/v_pages (A, P, nkv, pg, hd) — the WHOLE head-major
    page pool (page 0 of each layer = trash) and ``layer`` — the layer
    this call reads and writes, an int or a traced int32 scalar that
    rides the scalar-prefetch block (``ragged_paged_decode_attention``
    says how); page_table (b, W) int32; lengths (b,)
    int32 — tokens cached per row BEFORE this chunk; chunk_real (b,)
    int32 — real tokens in this chunk (c - left pad).  Real token i of
    the chunk lands at absolute position ``lengths[r] + i - pad`` and
    every query attends positions ``[0, its own position]`` — the causal
    rule over prefix + fresh chunk.

    Int8 page pools pass this layer's four (P, nkv) f32 scale arrays — OLD
    and NEW per K/V, the NEW ones pre-planned by
    ``models/attention._chunk_page_scales`` (the caller scatters them
    into its scale arrays; this kernel only READS scales) — and the
    fused write quantizes the chunk's K/V before the one-hot merge
    while old rows requantize under the grown scale; the attend runs
    on the dequantized merged tile.

    Returns (o (b, c, nh, hd), k_pages', v_pages'): the whole pools, of
    which only ``layer``'s owned pages (and its trash page) changed.  The
    page-pool outputs alias their inputs, so a caller that carries the
    pool through its layer loop and donates it (the chunk step) has one
    buffer from entry to exit.  Numerics match the lax fallback (scatter + gather +
    ``models/attention._sdpa_positions``; int8: requant-merge +
    dequantizing gather) to fp tolerance; one jit trace covers every
    (lengths, chunk_real) mix at a fixed (b, c, W) layout
    (``TRACE_COUNTS["ragged_prefill"]``).  ``interpret=None``
    auto-selects the Pallas interpreter off-TPU.
    """
    interpret = resolve_interpret(interpret)
    TRACE_COUNTS["ragged_prefill"] += 1
    quant = k_scale_old is not None
    b, c, nh, hd = q.shape
    _, P, nkv, pg, _ = k_pages.shape
    W = page_table.shape[1]
    if nh % nkv:
        raise ValueError(f"num_heads {nh} not a multiple of kv heads {nkv}")
    rep = nh // nkv
    # queries head-major with (chunk idx, GQA rep) fused into the sublane
    # dim: s = i*rep + e.  Sublane pads attend real keys and are sliced
    # off; chunk-KV sublane pads are never selected by the write one-hot.
    Q = c * rep
    Q8 = -(-Q // 8) * 8
    qh = jnp.moveaxis(q.reshape(b, c, nkv, rep, hd), 1, 2)
    qh = qh.reshape(b, nkv, Q, hd)
    if Q8 != Q:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, Q8 - Q), (0, 0)))
    C8 = -(-c // 8) * 8
    kc = jnp.moveaxis(k_chunk, 2, 1)                     # (b, nkv, c, hd)
    vc = jnp.moveaxis(v_chunk, 2, 1)
    if C8 != c:
        cpad = ((0, 0), (0, 0), (0, C8 - c), (0, 0))
        kc, vc = jnp.pad(kc, cpad), jnp.pad(vc, cpad)

    grid = (b, nkv, W)
    # index maps take the grid ids plus EVERY scalar-prefetch operand
    # (4 plain, 8 with the int8 scale arrays) — *pf absorbs the extras
    q_spec = pl.BlockSpec(
        (1, 1, Q8, hd), lambda r, h, j, *pf: (r, h, 0, 0)
    )
    c_spec = pl.BlockSpec(
        (1, 1, C8, hd), lambda r, h, j, *pf: (r, h, 0, 0)
    )
    # the layer dimension is squeezed: the kernel sees (1, 1, pg, hd)
    kv_in_spec = pl.BlockSpec(
        (None, 1, 1, pg, hd),
        lambda r, h, j, lyr, tbl, *pf: (lyr[0], tbl[r, j], h, 0, 0),
    )

    def kv_out_idx(r, h, j, lyr, tbl, ln, cr, *pf):
        # only the one cell owning a chunk-written page may flush to it;
        # everything else (pure-prefix pages, pages past the extent)
        # flushes its block to the layer's trash page — whose content is
        # garbage by design and never read
        takes_write = (j * pg + pg > ln[r]) & (j * pg < ln[r] + cr[r])
        return (lyr[0], jnp.where(takes_write, tbl[r, j], 0), h, 0, 0)

    kv_out_spec = pl.BlockSpec((None, 1, 1, pg, hd), kv_out_idx)

    prefetch = (_layer_operand(layer), page_table.astype(jnp.int32),
                lengths.astype(jnp.int32), chunk_real.astype(jnp.int32))
    if quant:
        prefetch += (k_scale_old.astype(jnp.float32),
                     k_scale_new.astype(jnp.float32),
                     v_scale_old.astype(jnp.float32),
                     v_scale_new.astype(jnp.float32))
    npre = len(prefetch)
    out, kp, vp = pl.pallas_call(
        functools.partial(
            _rpp_kernel, nw=W, pg=pg, c=c, rep=rep,
            sm_scale=1.0 / math.sqrt(hd), quant=quant,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=npre,
            grid=grid,
            in_specs=[q_spec, c_spec, c_spec, kv_in_spec, kv_in_spec],
            out_specs=[q_spec, kv_out_spec, kv_out_spec],
            scratch_shapes=[
                pltpu.VMEM((Q8, 128), jnp.float32),
                pltpu.VMEM((Q8, 128), jnp.float32),
                pltpu.VMEM((Q8, hd), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, nkv, Q8, hd), q.dtype),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        # the page-pool inputs (last two operands after the scalar
        # prefetch block) alias the page-pool outputs, all layers of
        # them: the write is in place under donation
        input_output_aliases={npre + 3: 1, npre + 4: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="ragged_paged_prefill_attention",
    )(*prefetch, qh, kc, vc, k_pages, v_pages)

    o = out[:, :, :Q].reshape(b, nkv, c, rep, hd)
    o = jnp.moveaxis(o, 1, 2).reshape(b, c, nh, hd)
    return o, kp, vp
