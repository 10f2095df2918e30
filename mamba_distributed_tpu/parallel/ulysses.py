"""Ulysses-style sequence parallelism for attention: all-to-all head sharding.

The second of the two attention SP strategies in SURVEY.md §2.3 (ring
attention is the first).  Where ring keeps heads whole and rotates KV
shards around the ``seq`` axis (S-1 ppermute hops of O(t_local) KV),
Ulysses re-distributes ONCE: an all-to-all turns the sequence sharding
into a head sharding, every device then runs ordinary *full-sequence*
causal attention for its slice of heads (via the same blockwise
online-softmax kernel the dense path uses), and a second all-to-all
restores the sequence sharding.

Trade-off (why both exist): Ulysses moves O(t·d/S) activation bytes
twice but computes each head's attention with zero inner-loop
communication — better when ICI all-to-all is cheap and heads are
plentiful; ring never materializes the full sequence on any chip —
mandatory when t/S is the memory budget.  Both are exact.

Constraints: num_heads % S == 0 and num_kv_heads % S == 0 (contiguous
head slices keep GQA groups aligned: q slice i maps exactly onto kv
slice i).  Configs that violate this should use ring attention.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def ulysses_attention(seq_ctx, q, k, v, impl: str = "xla"):
    """q (b, t, nh, hd), k/v (b, t, nkv, hd), t sharded over seq_ctx.axis.

    Returns (b, t, nh, hd) in q.dtype — exact match with single-device
    causal attention (pinned by tests/test_seq_parallel.py).  ``impl``
    picks the per-device SDPA backend: "xla" (blockwise scan) or
    "pallas" (flash kernel) — after the first all-to-all every device
    holds full-length sequences for its head slice, so the dense kernels
    drop in unchanged.
    """
    if impl == "pallas":
        from mamba_distributed_tpu.ops.pallas.attention_kernels import (
            flash_sdpa_causal as sdpa,
        )
    else:
        from mamba_distributed_tpu.ops.blockwise_attention import (
            blockwise_sdpa_causal as sdpa,
        )

    ctx = seq_ctx
    n = ctx.size
    nh, nkv = q.shape[2], k.shape[2]
    if nh % n or nkv % n:
        raise ValueError(
            f"ulysses_attention needs num_heads ({nh}) and num_kv_heads "
            f"({nkv}) divisible by the seq axis size ({n}); use ring "
            "attention for this config"
        )
    bat4 = P(ctx.batch_axes, ctx.axis, None, None)

    def local(q_l, k_l, v_l):
        # seq-sharded -> head-sharded: split heads over the axis,
        # concatenate the sequence back to full length.  K and V share a
        # shape, so they ride ONE stacked collective instead of two.
        qh = jax.lax.all_to_all(
            q_l, ctx.axis, split_axis=2, concat_axis=1, tiled=True
        )
        kv = jax.lax.all_to_all(
            jnp.stack([k_l, v_l]), ctx.axis, split_axis=3, concat_axis=2,
            tiled=True,
        )
        out = sdpa(qh, kv[0], kv[1])
        # head-sharded -> seq-sharded
        return jax.lax.all_to_all(
            out, ctx.axis, split_axis=1, concat_axis=2, tiled=True
        )

    fn = jax.shard_map(
        local, mesh=ctx.mesh, in_specs=(bat4, bat4, bat4), out_specs=bat4,
        check_vma=False,
    )
    return fn(q, k, v)
