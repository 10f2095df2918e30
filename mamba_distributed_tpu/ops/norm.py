"""RMSNorm family, TPU-native.

Equivalent of the reference dependency's fused Triton layernorm kernels
(``mamba_ssm/ops/triton/layernorm.py`` and ``layernorm_gated.py``, used via
``fused_add_norm=True`` — the MambaConfig default the reference runs with).
On TPU we express the math in plain JAX and let XLA fuse the residual add,
the normalization, and the neighbouring matmul prologue — elementwise
chains like these are exactly what the XLA fusion pass exists for, so a
hand-written Pallas kernel is deliberately not used unless a traced
benchmark run (``device time by scope``: ``gate_norm``, ``layers``) ever
shows the fusion breaking.

Matches the reference semantics: the residual stream is carried in fp32
(``residual_in_fp32=True``), normalization statistics are computed in fp32,
and the output is cast back to the compute dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMSNorm in fp32, output cast back to x.dtype."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def add_rms_norm(
    x: jax.Array,
    residual: jax.Array | None,
    weight: jax.Array,
    eps: float = 1e-5,
    residual_dtype: jnp.dtype = jnp.float32,
):
    """Fused residual-add + RMSNorm (prenorm form).

    Computes ``new_residual = x + residual`` (in ``residual_dtype``) and
    returns ``(rms_norm(new_residual), new_residual)`` — the same contract as
    the Triton ``layer_norm_fn(..., prenorm=True)`` path the reference's
    dependency uses between blocks.
    """
    r = x.astype(residual_dtype)
    if residual is not None:
        r = r + residual.astype(residual_dtype)
    return rms_norm(r, weight, eps).astype(x.dtype), r


def rms_norm_gated(
    x: jax.Array,
    z: jax.Array,
    weight: jax.Array,
    eps: float = 1e-5,
    group_size: int | None = None,
) -> jax.Array:
    """Gated RMSNorm: ``rms_norm(x * silu(z))``.

    Equivalent of ``RMSNormGated(norm_before_gate=False)`` used inside the
    Mamba-2 mixer (``mamba_ssm/ops/triton/layernorm_gated.py``).  When
    ``group_size`` is given, statistics are computed per contiguous group
    (grouped RMSNorm, used with ngroups > 1 / tensor parallelism).
    """
    xf = x.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    d = xf.shape[-1]
    if group_size is None or group_size == d:
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps)
    else:
        assert d % group_size == 0
        g = d // group_size
        xg = xf.reshape(*xf.shape[:-1], g, group_size)
        var = jnp.mean(jnp.square(xg), axis=-1, keepdims=True)
        y = (xg * jax.lax.rsqrt(var + eps)).reshape(xf.shape)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)
