"""Causal depthwise 1-D convolution, TPU-native.

TPU-native equivalent of the ``causal-conv1d`` CUDA package the reference
depends on (reference requirements.txt:1; ``causal_conv1d/csrc/*.cu`` in
Dao-AILab/causal-conv1d >= 1.4.0): the short (width-4) causal conv inside
every Mamba block, plus the O(1) single-step ``update`` used for recurrent
decode.

For a width-4 depthwise conv, the fastest XLA formulation is a sum of k
shifted elementwise multiply-adds (pure VPU work that XLA fuses into the
surrounding ops) rather than a general conv op.  The ``initial_state``
argument doubles as the decode cache and as the halo received from the
previous shard under sequence parallelism (SURVEY.md section 5).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from mamba_distributed_tpu.obs import scopes


@jax.named_scope(scopes.CONV)
def causal_conv1d(
    x: jax.Array,
    weight: jax.Array,
    bias: jax.Array | None = None,
    activation: str | None = "silu",
    initial_state: jax.Array | None = None,
    return_final_state: bool = False,
    impl: str = "shift",
):
    """Causal depthwise conv over the time axis.

    Args:
      x: (batch, seqlen, dim) input.
      weight: (dim, width) depthwise filter.
      bias: optional (dim,).
      activation: None | "silu".
      initial_state: optional (batch, width-1, dim) — the last ``width-1``
        inputs preceding ``x`` (zeros if None).  Used for decode prefill
        continuation and for sequence-parallel halo exchange.
      return_final_state: if True also return the new (batch, width-1, dim)
        state (the last width-1 columns of the padded input).

    Returns:
      y of shape (batch, seqlen, dim) [, final_state].
    """
    b, t, d = x.shape
    dim, width = weight.shape
    assert dim == d, (dim, d)
    if initial_state is None:
        pad = jnp.zeros((b, width - 1, d), dtype=x.dtype)
    else:
        assert initial_state.shape == (b, width - 1, d), initial_state.shape
        pad = initial_state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)  # (b, t + width - 1, d)
    if impl == "xla_conv":
        # grouped conv_general_dilated — XLA's dedicated depthwise path,
        # one op instead of `width` shifted adds.  Sweepable alternative:
        # the round-4 trace showed the shifted-add formulation dragging
        # the activation layout time-minor (pads/copies around the conv).
        # XLA convs are cross-correlations, so tap order matches as-is.
        y = jax.lax.conv_general_dilated(
            xp.astype(jnp.float32),
            weight.astype(jnp.float32)[:, None, :],  # OIW = (d, 1, width)
            window_strides=(1,),
            padding="VALID",
            dimension_numbers=("NWC", "OIW", "NWC"),
            feature_group_count=d,
        )
    elif impl == "shift":
        y = jnp.zeros((b, t, d), dtype=jnp.promote_types(x.dtype, jnp.float32))
        for i in range(width):
            # tap i sees input shifted by (width - 1 - i) steps into the past
            y = y + xp[:, i : i + t, :].astype(y.dtype) * weight[:, i].astype(y.dtype)
    else:
        raise ValueError(f"unsupported conv impl: {impl}")
    if bias is not None:
        y = y + bias.astype(y.dtype)
    if activation == "silu":
        y = jax.nn.silu(y)
    elif activation is not None:
        raise ValueError(f"unsupported activation: {activation}")
    y = y.astype(x.dtype)
    if return_final_state:
        final_state = xp[:, t:, :]  # last width-1 inputs
        return y, final_state
    return y


@jax.named_scope(scopes.CONV)
def causal_conv1d_update(
    x_t: jax.Array,
    conv_state: jax.Array,
    weight: jax.Array,
    bias: jax.Array | None = None,
    activation: str | None = "silu",
    state_mask: jax.Array | None = None,
):
    """O(1) single-token conv step for recurrent decode.

    Equivalent of ``causal_conv1d_update.cu`` in the reference's dependency.

    Args:
      x_t: (batch, dim) current-token input.
      conv_state: (batch, width-1, dim) previous inputs (oldest first).
      weight: (dim, width); bias: optional (dim,).
      state_mask: optional (batch,) bool; rows where it is False get their
        ``conv_state`` back unchanged (``None`` advances every row).

    Returns:
      (y_t of shape (batch, dim), new_conv_state).
    """
    b, d = x_t.shape
    dim, width = weight.shape
    assert dim == d
    window = jnp.concatenate([conv_state, x_t[:, None, :]], axis=1)  # (b, width, d)
    y = jnp.einsum("bwd,dw->bd", window.astype(jnp.float32), weight.astype(jnp.float32))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    if activation == "silu":
        y = jax.nn.silu(y)
    elif activation is not None:
        raise ValueError(f"unsupported activation: {activation}")
    new_state = window[:, 1:, :]
    if state_mask is not None:
        new_state = jnp.where(state_mask[:, None, None], new_state, conv_state)
    return y.astype(x_t.dtype), new_state
