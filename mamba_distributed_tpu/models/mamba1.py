"""Mamba-1 mixer (selective scan), TPU-native.

Functional equivalent of ``mamba_ssm.modules.mamba_simple.Mamba`` (mamba-ssm
2.2.2) — the mixer the reference's default ``ssm_cfg={}`` actually builds
(SURVEY.md §2.4 discrepancy).  Compute rides the in-tree chunked selective
scan (`ops/scan.py`) instead of the CUDA kernel.

Forward:  u -> in_proj -> split(x, z) -> causal_conv1d(x) ->
          x_proj -> (dt, B, C) -> dt_proj -> selective_scan(..., z=z) ->
          out_proj
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
from mamba_distributed_tpu.ops.conv import causal_conv1d, causal_conv1d_update
from mamba_distributed_tpu.ops.scan import selective_scan, selective_state_update


def init_mamba1_params(key: jax.Array, cfg: ModelConfig) -> dict:
    di = cfg.d_inner
    ds = cfg.effective_d_state
    dtr = cfg.effective_dt_rank
    k_in, k_conv, k_x, k_dtw, k_dtb, k_out = jax.random.split(key, 6)

    # dt_proj weight: U(+-dt_rank^-0.5 * dt_scale) for "random",
    # constant for "constant" (mamba_simple.py dt_init branch)
    dt_init_std = dtr**-0.5 * cfg.dt_scale
    if cfg.dt_init == "random":
        dt_w = jax.random.uniform(
            k_dtw, (dtr, di), jnp.float32, -dt_init_std, dt_init_std
        )
    elif cfg.dt_init == "constant":
        dt_w = jnp.full((dtr, di), dt_init_std, jnp.float32)
    else:
        raise ValueError(cfg.dt_init)

    # S4D-real init: A[d, n] = n+1  ->  A_log = log(A)
    A = jnp.tile(jnp.arange(1, ds + 1, dtype=jnp.float32)[None, :], (di, 1))

    params = {
        "in_proj": init_linear(k_in, cfg.d_model, 2 * di, cfg.proj_bias),
        "conv": init_conv(k_conv, di, cfg.d_conv, cfg.conv_bias),
        "x_proj": init_linear(k_x, di, dtr + 2 * ds, False),
        "dt_proj": {
            "kernel": dt_w,
            "bias": init_dt_bias(
                k_dtb, (di,), cfg.dt_min, cfg.dt_max, cfg.dt_init_floor
            ),
        },
        "A_log": jnp.log(A),
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": init_linear(k_out, di, cfg.d_model, cfg.proj_bias),
    }
    if cfg.rescale_prenorm_residual:
        n_residuals = 2 if cfg.d_intermediate > 0 else 1
        params["out_proj"]["kernel"] = params["out_proj"]["kernel"] / math.sqrt(
            n_residuals * cfg.n_layer
        )
    return params


def mamba1_mixer(
    params: dict,
    cfg: ModelConfig,
    u: jax.Array,
    initial_conv_state: jax.Array | None = None,
    initial_ssm_state: jax.Array | None = None,
    return_final_state: bool = False,
    seq_ctx=None,
    token_mask: jax.Array | None = None,
):
    """Full-sequence Mamba-1 mixer forward.

    u (b, t, d_model) -> y (b, t, d_model) [, (conv_state, ssm_state)].

    ``token_mask`` (b, t) {0,1} zeroes the conv/scan inputs at masked
    positions (left-padded bucketed prefill, inference/bucketing.py):
    with x=0 the selective scan's update term dt*B*x vanishes and the
    state only decays, so a zero initial state stays zero through the
    pad prefix — same contract as mamba2_mixer.
    """
    di = cfg.d_inner
    ds = cfg.effective_d_state
    dtr = cfg.effective_dt_rank
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    check_no_decode_state_under_sp(
        seq_ctx, initial_conv_state, initial_ssm_state, return_final_state
    )

    xz = linear(params["in_proj"], u, compute_dtype)
    x, z = xz[..., :di], xz[..., di:]

    if token_mask is not None:
        if seq_ctx is not None:
            raise ValueError("token_mask is a single-device prefill feature")
        x = x * token_mask[..., None].astype(x.dtype)
    if seq_ctx is not None:
        from mamba_distributed_tpu.parallel.seq_parallel import sp_conv1d

        x, conv_state = sp_conv1d(
            seq_ctx, x, params["conv"]["kernel"],
            params["conv"].get("bias"), "silu",
        )
    else:
        x, conv_state = causal_conv1d(
            x, params["conv"]["kernel"], params["conv"].get("bias"),
            activation="silu",
            initial_state=initial_conv_state,
            return_final_state=True,
            impl=cfg.conv_impl,
        )
    if token_mask is not None:
        x = x * token_mask[..., None].astype(x.dtype)

    x_db = linear(params["x_proj"], x, compute_dtype)
    dt = x_db[..., :dtr]
    B = x_db[..., dtr : dtr + ds].astype(jnp.float32)
    C = x_db[..., dtr + ds :].astype(jnp.float32)
    # dt_proj without bias; the bias folds into the scan's delta_bias so the
    # softplus happens in fp32 inside the kernel (selective_scan_interface
    # does the same).
    dt = jnp.dot(
        dt.astype(compute_dtype),
        params["dt_proj"]["kernel"].astype(compute_dtype),
        preferred_element_type=jnp.float32,
    )

    A = -jnp.exp(params["A_log"])  # (di, ds)
    scan_kw = dict(
        D=params["D"], z=z, delta_bias=params["dt_proj"]["bias"],
        delta_softplus=True,
    )
    if seq_ctx is not None:
        from mamba_distributed_tpu.parallel.seq_parallel import sp_selective_scan

        y, ssm_state = sp_selective_scan(
            seq_ctx, x, dt, A, B, C, ssm_impl=cfg.ssm_impl, **scan_kw
        )
    else:
        if cfg.ssm_impl == "pallas":
            from mamba_distributed_tpu.ops.pallas import selective_scan_pallas

            scan_fn = selective_scan_pallas
        else:
            scan_fn = selective_scan
        if initial_ssm_state is None and not return_final_state:
            # training path: keeps the Pallas backend on its custom-vjp route
            y = scan_fn(x, dt, A, B, C, **scan_kw)
            ssm_state = None
        else:
            y, ssm_state = scan_fn(
                x, dt, A, B, C, **scan_kw,
                initial_state=initial_ssm_state, return_final_state=True,
            )
    # remat_policy="mixer" save point (models/lm.py:_remat)
    y = checkpoint_name(y, "mixer_out")
    out = linear(params["out_proj"], y, compute_dtype)
    if return_final_state:
        return out, (conv_state, ssm_state)
    return out


def init_mamba1_state(cfg: ModelConfig, batch: int, dtype=None):
    """conv cache in the compute dtype (matches full-sequence prefill);
    SSM state fp32 (matches the scan's carry)."""
    di = cfg.d_inner
    ds = cfg.effective_d_state
    if dtype is None:
        dtype = jnp.dtype(cfg.compute_dtype)
    conv_state = jnp.zeros((batch, cfg.d_conv - 1, di), dtype)
    ssm_state = jnp.zeros((batch, di, ds), jnp.float32)
    return conv_state, ssm_state


def mamba1_mixer_step(
    params: dict,
    cfg: ModelConfig,
    u_t: jax.Array,
    conv_state: jax.Array,
    ssm_state: jax.Array,
    state_mask: jax.Array | None = None,
):
    """O(1) single-token decode step for Mamba-1.  ``state_mask`` as in
    ``mamba2_mixer_step``: False rows keep both states bit for bit."""
    di = cfg.d_inner
    ds = cfg.effective_d_state
    dtr = cfg.effective_dt_rank
    compute_dtype = jnp.dtype(cfg.compute_dtype)

    xz = linear(params["in_proj"], u_t, compute_dtype)
    x, z = xz[..., :di], xz[..., di:]

    x, conv_state = causal_conv1d_update(
        x, conv_state, params["conv"]["kernel"], params["conv"].get("bias"),
        activation="silu", state_mask=state_mask,
    )
    x_db = linear(params["x_proj"], x, compute_dtype)
    dt = x_db[..., :dtr]
    B = x_db[..., dtr : dtr + ds].astype(jnp.float32)
    C = x_db[..., dtr + ds :].astype(jnp.float32)
    dt = jnp.dot(
        dt.astype(compute_dtype),
        params["dt_proj"]["kernel"].astype(compute_dtype),
        preferred_element_type=jnp.float32,
    )
    A = -jnp.exp(params["A_log"])
    y, ssm_state = selective_state_update(
        ssm_state, x, dt, A, B, C,
        D=params["D"], z_t=z,
        dt_bias=params["dt_proj"]["bias"], dt_softplus=True,
        state_mask=state_mask,
    )
    out = linear(params["out_proj"], y, compute_dtype)
    return out, (conv_state, ssm_state)
