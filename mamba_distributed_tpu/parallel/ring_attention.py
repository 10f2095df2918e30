"""Ring attention over the sequence mesh axis (hybrid config 5).

Blockwise causal attention with online-softmax accumulation: each device
keeps its local Q block and rotates KV blocks around the ``seq`` ring via
``ppermute`` — S-1 hops of the local KV instead of an all-gather of the
whole sequence.  Causality is enforced per (q-block, kv-block) pair from
the global block indices; fully-future blocks are computed-and-masked
(compute is uniform, which XLA/TPU prefers over divergent control flow).

Within each hop the received KV shard is consumed in flash-style
sub-blocks (ops/blockwise_attention.py — the same update the dense path
uses), so the per-hop working set is O(t_local * block), never the
(t_local, t_local) fp32 score slab.

The math follows the published blockwise/ring-attention construction
(Liu et al. 2023); the implementation is an in-tree shard_map + lax.scan.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from mamba_distributed_tpu.ops.blockwise_attention import (
    DEFAULT_BLOCK,
    ols_block_update,
    ols_finalize,
    ols_init,
)
from mamba_distributed_tpu.ops.scan import _divisor_chunk


def ring_attention(seq_ctx, q, k, v, k_block: int = DEFAULT_BLOCK,
                   impl: str = "xla"):
    """q (b, t, nh, hd), k/v (b, t, nkv, hd), t sharded over seq_ctx.axis.

    Returns (b, t, nh, hd) in q.dtype.  Exact (up to fp32 softmax) match
    with single-device causal attention — pinned by tests.  ``impl``
    picks the per-hop SDPA: "xla" (blockwise scan below) or "pallas"
    (flash kernels per hop, _ring_attention_pallas).
    """
    if impl == "pallas":
        return _ring_attention_pallas(seq_ctx, q, k, v)
    ctx = seq_ctx
    n = ctx.size
    b, t, nh, hd = q.shape
    nkv = k.shape[2]
    rep = nh // nkv
    bat4 = P(ctx.batch_axes, ctx.axis, None, None)

    def local(q_l, k_l, v_l):
        bl, tl, _, _ = q_l.shape
        my = jax.lax.axis_index(ctx.axis)
        qh = q_l.reshape(bl, tl, nkv, rep, hd)
        qpos = my * tl + jnp.arange(tl)
        kb = _divisor_chunk(tl, k_block)
        nkb = tl // kb

        perm = [(i, (i + 1) % n) for i in range(n)]

        def accumulate(acc, kv, i):
            k_i, v_i = kv
            # kv shard currently held came from rank (my - i) mod n
            src = (my - i) % n
            ks = jnp.moveaxis(k_i.reshape(bl, nkb, kb, nkv, hd), 1, 0)
            vs = jnp.moveaxis(v_i.reshape(bl, nkb, kb, nkv, hd), 1, 0)

            def kv_step(a, inp):
                kj, k_b, v_b = inp
                kpos = src * tl + kj * kb + jnp.arange(kb)
                return ols_block_update(a, qh, k_b, v_b, qpos, kpos), None

            acc, _ = jax.lax.scan(kv_step, acc, (jnp.arange(nkb), ks, vs))
            return acc

        def step(carry, i):
            kv, acc = carry
            acc = accumulate(acc, kv, i)
            kv = jax.lax.ppermute(kv, ctx.axis, perm)
            return (kv, acc), None

        # n-1 hops; the last shard is consumed without a wasted final permute
        (kv, acc), _ = jax.lax.scan(
            step, ((k_l, v_l), ols_init(bl, nkv, rep, tl, hd)),
            jnp.arange(n - 1),
        )
        acc = accumulate(acc, kv, n - 1)
        return ols_finalize(acc, q_l.dtype)

    fn = jax.shard_map(
        local, mesh=ctx.mesh, in_specs=(bat4, bat4, bat4), out_specs=bat4,
        check_vma=False,
    )
    return fn(q, k, v)


# ---------------------------------------------------------------------------
# Ring attention on the Pallas flash kernels (attn_impl="pallas").
#
# Every hop of a causal ring is one of exactly three cases relative to the
# local Q shard — fully-past (the kv shard's owner precedes this device:
# every pair is unmasked, static offset = t_local), diagonal (own shard:
# ordinary causal, offset = 0), or fully-future (skipped outright, saving
# the compute the XLA path spends computing-and-masking).  That makes the
# traced per-hop offset problem disappear: ``lax.switch`` picks between
# two static-offset flash calls and a skip.
#
# Per-hop partials (o_i, lse_i) merge in XLA by the standard logsumexp
# combination; the backward exploits that the flash decomposition is
# exact per (q, kv) pair GIVEN the merged lse and delta = rowsum(dO*O):
# dq accumulates locally over hops, dk/dv ride the ring together with
# their kv shard for one full cycle (n hops), landing home fully
# accumulated.  This is the ring analogue of the dense kernel's
# custom_vjp, so the whole thing is differentiable end to end.
# ---------------------------------------------------------------------------


def _merge_partial(m, num, den, o_i, lse_i):
    """Fold one hop's normalized partial (o_i, lse_i) into the running
    (max, numerator, denominator) accumulator (all fp32)."""
    m_new = jnp.maximum(m, lse_i)
    w_prev = jnp.where(jnp.isfinite(m), jnp.exp(m - m_new), 0.0)
    w_i = jnp.where(jnp.isfinite(lse_i), jnp.exp(lse_i - m_new), 0.0)
    num = num * w_prev[..., None] + o_i.astype(jnp.float32) * w_i[..., None]
    den = den * w_prev + w_i
    return m_new, num, den


def _ring_attention_pallas(seq_ctx, q, k, v):
    from mamba_distributed_tpu.ops.pallas.attention_kernels import (
        flash_pair_dkv,
        flash_pair_dq,
        flash_pair_fwd,
    )

    ctx = seq_ctx
    n = ctx.size
    nh = q.shape[2]
    nkv = k.shape[2]
    bat4 = P(ctx.batch_axes, ctx.axis, None, None)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def local(q_l, k_l, v_l):
        bl, tl, _, hd = q_l.shape
        qt0 = jnp.moveaxis(q_l, 2, 1)                # (b, nh, tl, hd)
        kt0 = jnp.moveaxis(k_l, 2, 1)                # (b, nkv, tl, hd)
        vt0 = jnp.moveaxis(v_l, 2, 1)

        def hop_branchno(i):
            # 0: fully-past (src < my), 1: diagonal, 2: fully-future.
            # axis_index is taken HERE (inside the traced fwd/bwd), never
            # closed over by the custom_vjp — closures over tracers leak.
            my = jax.lax.axis_index(ctx.axis)
            src = (my - i) % n
            return jnp.where(src < my, 0, jnp.where(src == my, 1, 2))

        @jax.custom_vjp
        def ring_core(qt, kt0, vt0):
            o, _ = _ring_fwd_impl(qt, kt0, vt0)
            return o

        def _ring_fwd_impl(qt, kt0, vt0):
            def pair_case(offset):
                def run(kt, vt):
                    return flash_pair_fwd(qt, kt, vt, offset)
                return run

            def skip_case(kt, vt):
                return (
                    jnp.zeros(qt.shape, qt.dtype),
                    jnp.full(qt.shape[:3], -jnp.inf, jnp.float32),
                )

            def fold(acc, kt, vt, i):
                o_i, lse_i = jax.lax.switch(
                    hop_branchno(i),
                    [pair_case(tl), pair_case(0), skip_case],
                    kt, vt,
                )
                return _merge_partial(*acc, o_i, lse_i)

            acc0 = (
                jnp.full(qt.shape[:3], -jnp.inf, jnp.float32),
                jnp.zeros(qt.shape, jnp.float32),
                jnp.zeros(qt.shape[:3], jnp.float32),
            )

            def step(carry, i):
                (kt, vt), acc = carry
                acc = fold(acc, kt, vt, i)
                kt, vt = jax.lax.ppermute((kt, vt), ctx.axis, perm)
                return ((kt, vt), acc), None

            # n-1 hops; the last shard is consumed without a final permute
            ((kt, vt), acc), _ = jax.lax.scan(
                step, ((kt0, vt0), acc0), jnp.arange(n - 1)
            )
            m, num, den = fold(acc, kt, vt, jnp.int32(n - 1))
            o = (num / jnp.maximum(den, 1e-30)[..., None]).astype(qt.dtype)
            lse = jnp.where(
                den > 0.0, m + jnp.log(jnp.maximum(den, 1e-30)), jnp.inf
            )
            return o, lse

        def ring_fwd(qt, kt0, vt0):
            o, lse = _ring_fwd_impl(qt, kt0, vt0)
            return o, (qt, kt0, vt0, o, lse)

        def ring_bwd(res, do):
            qt, kt0, vt0, o, lse = res
            dlt = jnp.sum(
                do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
            )

            def dq_case(offset):
                def run(kt, vt):
                    return flash_pair_dq(qt, kt, vt, do, lse, dlt, offset)
                return run

            def dq_skip(kt, vt):
                return jnp.zeros(qt.shape, jnp.float32)

            def dkv_case(offset):
                def run(kt, vt):
                    return flash_pair_dkv(qt, kt, vt, do, lse, dlt, offset)
                return run

            def dkv_skip(kt, vt):
                return (
                    jnp.zeros(kt.shape, jnp.float32),
                    jnp.zeros(vt.shape, jnp.float32),
                )

            def step(carry, i):
                (kt, vt, dk, dv), dq = carry
                bno = hop_branchno(i)
                dq = dq + jax.lax.switch(
                    bno, [dq_case(tl), dq_case(0), dq_skip], kt, vt
                )
                dk_i, dv_i = jax.lax.switch(
                    bno, [dkv_case(tl), dkv_case(0), dkv_skip], kt, vt
                )
                # dk/dv ride the ring WITH their kv shard: after the full
                # n-hop cycle each shard's gradient lands back home
                kt, vt, dk, dv = jax.lax.ppermute(
                    (kt, vt, dk + dk_i, dv + dv_i), ctx.axis, perm
                )
                return ((kt, vt, dk, dv), dq), None

            dk0 = jnp.zeros(kt0.shape, jnp.float32)
            dv0 = jnp.zeros(vt0.shape, jnp.float32)
            dq0 = jnp.zeros(qt.shape, jnp.float32)
            ((_, _, dk, dv), dq), _ = jax.lax.scan(
                step, ((kt0, vt0, dk0, dv0), dq0), jnp.arange(n)
            )
            return (
                dq.astype(qt.dtype), dk.astype(kt0.dtype),
                dv.astype(vt0.dtype),
            )

        ring_core.defvjp(ring_fwd, ring_bwd)

        out = ring_core(qt0, kt0, vt0)
        return jnp.moveaxis(out, 1, 2)               # (b, tl, nh, hd)

    fn = jax.shard_map(
        local, mesh=ctx.mesh, in_specs=(bat4, bat4, bat4), out_specs=bat4,
        check_vma=False,
    )
    return fn(q, k, v)
