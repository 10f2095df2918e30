"""Mamba-2 mixer (SSD), TPU-native.

Functional equivalent of ``mamba_ssm.modules.mamba2.Mamba2`` (mamba-ssm
2.2.2, pinned at reference requirements.txt:2), the headline mixer of
BASELINE.json.  Projection layout, dt/A/D parameterization, and the gated
RMSNorm placement follow that module's semantics; the compute path is the
in-tree TPU SSD (`ops/ssd.py`) instead of Triton kernels.

Forward:  u -> in_proj -> split(z, xBC, dt) -> causal_conv1d(xBC) ->
          split(x, B, C) -> SSD(x, dt, A, B, C, D) -> gated RMSNorm(y, z)
          -> out_proj
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from mamba_distributed_tpu.config import ModelConfig
from mamba_distributed_tpu.models.common import (
    check_no_decode_state_under_sp,
    init_conv,
    init_dt_bias,
    init_linear,
    linear,
)
from mamba_distributed_tpu.obs import scopes
from mamba_distributed_tpu.ops.conv import causal_conv1d, causal_conv1d_update
from mamba_distributed_tpu.ops.norm import rms_norm_gated
from mamba_distributed_tpu.ops.ssd import ssd_chunked, ssd_state_update


def _dims(cfg: ModelConfig):
    di = cfg.d_inner
    ds = cfg.effective_d_state
    g = cfg.ngroups
    nh = cfg.nheads
    d_in_proj = 2 * di + 2 * g * ds + nh
    conv_dim = di + 2 * g * ds
    return di, ds, g, nh, d_in_proj, conv_dim


def init_mamba2_params(key: jax.Array, cfg: ModelConfig) -> dict:
    di, ds, g, nh, d_in_proj, conv_dim = _dims(cfg)
    k_in, k_conv, k_dt, k_a, k_out = jax.random.split(key, 5)
    params = {
        "in_proj": init_linear(k_in, cfg.d_model, d_in_proj, cfg.proj_bias),
        "conv": init_conv(k_conv, conv_dim, cfg.d_conv, cfg.conv_bias),
        "dt_bias": init_dt_bias(
            k_dt, (nh,), cfg.dt_min, cfg.dt_max, cfg.dt_init_floor
        ),
        # A ~ U(a_init_min, a_init_max), stored as log (A = -exp(A_log))
        "A_log": jnp.log(
            jax.random.uniform(
                k_a, (nh,), jnp.float32, cfg.a_init_min, cfg.a_init_max
            )
        ),
        "D": jnp.ones((di if cfg.d_has_hdim else nh,), jnp.float32),
        "norm": {"weight": jnp.ones((di,), jnp.float32)},
        "out_proj": init_linear(k_out, di, cfg.d_model, cfg.proj_bias),
    }
    if cfg.rescale_prenorm_residual:
        n_residuals = 2 if cfg.d_intermediate > 0 else 1
        params["out_proj"]["kernel"] = params["out_proj"]["kernel"] / math.sqrt(
            n_residuals * cfg.n_layer
        )
    return params


def _split_zxbcdt(zxbcdt: jax.Array, cfg: ModelConfig):
    di, ds, g, nh, _, conv_dim = _dims(cfg)
    z = zxbcdt[..., :di]
    xBC = zxbcdt[..., di : di + conv_dim]
    dt = zxbcdt[..., di + conv_dim :]
    return z, xBC, dt


def _split_xbc(xBC: jax.Array, cfg: ModelConfig):
    di, ds, g, _, _, _ = _dims(cfg)
    x = xBC[..., :di]
    B = xBC[..., di : di + g * ds]
    C = xBC[..., di + g * ds :]
    return x, B, C


def mamba2_mixer(
    params: dict,
    cfg: ModelConfig,
    u: jax.Array,
    initial_conv_state: jax.Array | None = None,
    initial_ssm_state: jax.Array | None = None,
    return_final_state: bool = False,
    seq_ctx=None,
    token_mask: jax.Array | None = None,
):
    """Full-sequence Mamba-2 mixer forward.

    Args:
      u: (b, t, d_model) in compute dtype.
      initial_conv_state: (b, d_conv-1, conv_dim) decode/prefill carry
        (single-device only — mutually exclusive with ``seq_ctx``).
      initial_ssm_state: (b, nheads, headdim, d_state) carry (same).
      seq_ctx: optional ``parallel.seq_parallel.SeqContext`` — when given,
        the conv halo and SSD chunk-state passing run across the mesh's
        ``seq`` axis instead of locally; decode-state carry is rejected.
      token_mask: optional (b, t) {0,1} — zeroes the conv/SSM inputs at
        masked positions so a left-padded prompt produces the same scan
        state as the unpadded one (inference/bucketing.py).  Masked
        BEFORE the conv (pad inputs must look like the zero initial conv
        state) and AFTER it (the conv bias + silu would otherwise leak a
        nonzero x/B into the SSM update at pad positions).

    Returns: y (b, t, d_model) [, (conv_state, ssm_state)].
    """
    di, ds, g, nh, _, conv_dim = _dims(cfg)
    b, t, _ = u.shape
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    check_no_decode_state_under_sp(
        seq_ctx, initial_conv_state, initial_ssm_state, return_final_state
    )

    with jax.named_scope(scopes.MIXER_IN_PROJ):
        zxbcdt = linear(params["in_proj"], u, compute_dtype)
        z, xBC, dt = _split_zxbcdt(zxbcdt, cfg)

        if token_mask is not None:
            if seq_ctx is not None:
                raise ValueError(
                    "token_mask is a single-device prefill feature"
                )
            xBC = xBC * token_mask[..., None].astype(xBC.dtype)
    if seq_ctx is not None:
        from mamba_distributed_tpu.parallel.seq_parallel import sp_conv1d

        with jax.named_scope(scopes.CONV):
            xBC, conv_state = sp_conv1d(
                seq_ctx, xBC, params["conv"]["kernel"],
                params["conv"].get("bias"), "silu",
            )
    else:
        xBC, conv_state = causal_conv1d(
            xBC,
            params["conv"]["kernel"],
            params["conv"].get("bias"),
            activation="silu",
            initial_state=initial_conv_state,
            return_final_state=True,
            impl=cfg.conv_impl,
        )
    # the scan's inputs (head split, softplus of dt) count as the scan's
    with jax.named_scope(scopes.SSD):
        if token_mask is not None:
            xBC = xBC * token_mask[..., None].astype(xBC.dtype)
        x, B, C = _split_xbc(xBC, cfg)

        x = x.reshape(b, t, nh, cfg.headdim)
        B = B.reshape(b, t, g, ds)
        C = C.reshape(b, t, g, ds)
        dtf = jax.nn.softplus(
            dt.astype(jnp.float32) + params["dt_bias"][None, None, :]
        )
        A = -jnp.exp(params["A_log"])  # (nh,)
        D = (params["D"].reshape(nh, cfg.headdim) if cfg.d_has_hdim
             else params["D"])

    if seq_ctx is not None:
        from mamba_distributed_tpu.parallel.seq_parallel import sp_ssd

        with jax.named_scope(scopes.SSD):
            y, ssm_state = sp_ssd(
                seq_ctx, x, dtf, A, B, C, cfg.chunk_size, D,
                compute_dtype=compute_dtype, ssm_impl=cfg.ssm_impl,
            )
    elif cfg.ssm_impl == "pallas":
        from mamba_distributed_tpu.ops.pallas import ssd_chunked_pallas

        with jax.named_scope(scopes.SSD):
            if initial_ssm_state is None and not return_final_state:
                y = ssd_chunked_pallas(
                    x, dtf, A, B, C, chunk_size=cfg.chunk_size, D=D,
                    compute_dtype=compute_dtype,
                )
                ssm_state = None
            else:
                y, ssm_state = ssd_chunked_pallas(
                    x, dtf, A, B, C, chunk_size=cfg.chunk_size, D=D,
                    initial_state=initial_ssm_state,
                    return_final_state=True, compute_dtype=compute_dtype,
                )
    else:
        y, ssm_state = ssd_chunked(
            x, dtf, A, B, C,
            chunk_size=cfg.chunk_size,
            D=D,
            initial_state=initial_ssm_state,
            return_final_state=True,
            compute_dtype=compute_dtype,
        )
    # remat_policy="mixer": the scan output is the save point — the
    # backward then never recomputes the SSD scan, the priciest part of
    # the block (models/lm.py:_remat)
    y = checkpoint_name(y, "mixer_out")
    with jax.named_scope(scopes.GATE_NORM):
        y = y.reshape(b, t, di)
        y = rms_norm_gated(
            y, z, params["norm"]["weight"], cfg.norm_eps,
            group_size=di // g if g > 1 else None,
        )
    with jax.named_scope(scopes.MIXER_OUT_PROJ):
        out = linear(params["out_proj"], y, compute_dtype)
    if return_final_state:
        return out, (conv_state, ssm_state)
    return out


def init_mamba2_state(cfg: ModelConfig, batch: int, dtype=None):
    """Zero decode state: (conv_state, ssm_state) for one mixer.

    conv cache in the compute dtype (matching what the full-sequence
    prefill produces), SSM state in fp32 (matching state_passing).
    """
    di, ds, g, nh, _, conv_dim = _dims(cfg)
    if dtype is None:
        dtype = jnp.dtype(cfg.compute_dtype)
    conv_state = jnp.zeros((batch, cfg.d_conv - 1, conv_dim), dtype)
    ssm_state = jnp.zeros((batch, nh, cfg.headdim, ds), jnp.float32)
    return conv_state, ssm_state


def mamba2_mixer_step(
    params: dict,
    cfg: ModelConfig,
    u_t: jax.Array,
    conv_state: jax.Array,
    ssm_state: jax.Array,
    state_mask: jax.Array | None = None,
):
    """O(1) single-token decode step.

    u_t (b, d_model) -> (y_t (b, d_model), (conv_state, ssm_state)).
    Numerically matches the full-sequence path token-for-token (the decode
    parity test pins this).  ``state_mask`` (b,) bool: rows where it is
    False keep both states bit for bit (``lm_step`` says who and why);
    ``None`` advances every row.
    """
    di, ds, g, nh, _, conv_dim = _dims(cfg)
    b, _ = u_t.shape
    compute_dtype = jnp.dtype(cfg.compute_dtype)

    with jax.named_scope(scopes.MIXER_IN_PROJ):
        zxbcdt = linear(params["in_proj"], u_t, compute_dtype)
        z, xBC, dt = _split_zxbcdt(zxbcdt, cfg)

    xBC, conv_state = causal_conv1d_update(
        xBC, conv_state, params["conv"]["kernel"], params["conv"].get("bias"),
        activation="silu", state_mask=state_mask,
    )
    with jax.named_scope(scopes.SSD):
        x, B, C = _split_xbc(xBC, cfg)

        x = x.reshape(b, nh, cfg.headdim)
        B = B.reshape(b, g, ds)
        C = C.reshape(b, g, ds)
        A = -jnp.exp(params["A_log"])
        D = (params["D"].reshape(nh, cfg.headdim) if cfg.d_has_hdim
             else params["D"])

    y, ssm_state = ssd_state_update(
        ssm_state, x, dt.astype(jnp.float32), A, B, C, D,
        dt_bias=params["dt_bias"], dt_softplus=True, state_mask=state_mask,
    )
    with jax.named_scope(scopes.GATE_NORM):
        y = y.reshape(b, di)
        y = rms_norm_gated(
            y, z, params["norm"]["weight"], cfg.norm_eps,
            group_size=di // g if g > 1 else None,
        )
    with jax.named_scope(scopes.MIXER_OUT_PROJ):
        out = linear(params["out_proj"], y, compute_dtype)
    return out, (conv_state, ssm_state)
