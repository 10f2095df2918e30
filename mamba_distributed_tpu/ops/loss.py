"""Vocab-blocked cross-entropy: the LM-head matmul + softmax-CE without
ever materializing the (b, t, V) logits tensor.

The dense path (models/lm.py:lm_loss) computes logits once (824 MB bf16
at the 280M recipe, 3.3 GB at the reference's B=32 — reference
train.py:43 recipe) and saves them for the backward.  Here the head
matmul runs block-by-block over the vocab under ``lax.scan`` with an
online logsumexp carry, and the ``custom_vjp`` backward recomputes each
block's logits from the residuals — the activation-memory profile drops
from O(b·t·V) to O(b·t·block).

Numerics match the dense path: each block's logits go through the same
fp32-accumulate → compute-dtype round-trip the dense head performs
(models/lm.py:_final_logits), and the loss is the same
``mean(logsumexp - gathered logit)`` in fp32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from mamba_distributed_tpu.obs import scopes


def _block_logits(normed, head_blk, compute_dtype):
    """One vocab block of the head matmul, with the dense path's dtype
    round-trip (bf16 matmul, fp32 accumulate, compute-dtype output)."""
    out = jnp.dot(
        normed.astype(compute_dtype),
        head_blk.astype(compute_dtype).T,
        preferred_element_type=jnp.float32,
    )
    return out.astype(compute_dtype).astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def blocked_cross_entropy(
    normed: jax.Array,
    head: jax.Array,
    targets: jax.Array,
    n_blocks: int = 8,
    compute_dtype=jnp.bfloat16,
) -> jax.Array:
    """Mean CE over (b, t) positions; ``head`` is (V, d) — the tied
    embedding matrix (models/lm.py tied head) or ``lm_head.kernel.T``."""
    lse, tgt = _forward_scan(normed, head, targets, n_blocks, compute_dtype)
    return jnp.mean(lse - tgt)


def _forward_scan(normed, head, targets, n_blocks, compute_dtype):
    V, d = head.shape
    assert V % n_blocks == 0, (V, n_blocks)
    bs = V // n_blocks
    blocks = head.reshape(n_blocks, bs, d)

    def body(carry, head_blk):
        m, s, tgt, off = carry
        logits = _block_logits(normed, head_blk, compute_dtype)  # (b,t,bs)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[..., None]), axis=-1
        )
        in_blk = (targets >= off) & (targets < off + bs)
        idx = jnp.clip(targets - off, 0, bs - 1)
        tl = jnp.take_along_axis(logits, idx[..., None], axis=-1)[..., 0]
        tgt = jnp.where(in_blk, tl, tgt)
        return (m_new, s, tgt, off + bs), None

    b, t = targets.shape
    init = (
        jnp.full((b, t), -jnp.inf, jnp.float32),
        jnp.zeros((b, t), jnp.float32),
        jnp.zeros((b, t), jnp.float32),
        jnp.zeros((), jnp.int32),
    )
    (m, s, tgt, _), _ = jax.lax.scan(body, init, blocks)
    return m + jnp.log(s), tgt


# the scope is entered in _fwd and _bwd themselves: a custom_vjp's rules
# are traced apart from the call site, so its backward carries no name
# unless it is given one here
@jax.named_scope(scopes.LM_HEAD_LOSS)
def _fwd(normed, head, targets, n_blocks, compute_dtype):
    lse, tgt = _forward_scan(normed, head, targets, n_blocks, compute_dtype)
    return jnp.mean(lse - tgt), (normed, head, targets, lse)


@jax.named_scope(scopes.LM_HEAD_LOSS)
def _bwd(n_blocks, compute_dtype, res, g):
    normed, head, targets, lse = res
    V, d = head.shape
    bs = V // n_blocks
    blocks = head.reshape(n_blocks, bs, d)
    b, t = targets.shape
    scale = g / (b * t)  # d(mean)/d(per-position loss)

    def body(carry, head_blk):
        dnormed, off = carry
        logits = _block_logits(normed, head_blk, compute_dtype)
        p = jnp.exp(logits - lse[..., None])  # softmax block, fp32
        in_blk = (targets >= off) & (targets < off + bs)
        idx = jnp.clip(targets - off, 0, bs - 1)
        onehot = (
            jax.nn.one_hot(idx, bs, dtype=jnp.float32)
            * in_blk[..., None]
        )
        dl = ((p - onehot) * scale).astype(compute_dtype)  # (b,t,bs)
        dnormed = dnormed + jnp.einsum(
            "btv,vd->btd", dl, head_blk.astype(compute_dtype),
            preferred_element_type=jnp.float32,
        )
        dblk = jnp.einsum(
            "btv,btd->vd", dl, normed.astype(compute_dtype),
            preferred_element_type=jnp.float32,
        )
        return (dnormed, off + bs), dblk

    init = (jnp.zeros(normed.shape, jnp.float32), jnp.zeros((), jnp.int32))
    (dnormed, _), dhead = jax.lax.scan(body, init, blocks)
    # cast to head.dtype: custom_vjp cotangents must match the primal aval,
    # and head params may one day be held in bf16 (ADVICE r4)
    return (dnormed.astype(normed.dtype),
            dhead.reshape(V, d).astype(head.dtype), None)


blocked_cross_entropy.defvjp(_fwd, _bwd)
