"""Analytic FLOPs accounting for MFU (the BASELINE.json headline metric).

The reference publishes no MFU (SURVEY.md §6); this is the standard
matmul-dominated accounting: 2*m*n FLOPs per (m x n) matvec per token,
3x forward for a training step (fwd + 2x bwd), attention causally halved.

Two conventions (docs/KERNELS.md; the trainer logs ``model``):

- ``hardware``: counts what the chunked SSD algorithm actually executes,
  including the O(chunk) Gram/decay matmuls.  This measures how busy the
  MXU is, but flatters "useful work" MFU because the chunked formulation
  does more arithmetic than the recurrence it computes.
- ``model``: counts only the math the *model* defines — parameter matmuls
  plus the recurrent-formulation state update/readout (O(1) per token,
  no chunk-size term).  This is the 6ND-style number; the >=45% target
  is judged on this stricter convention.
"""

from __future__ import annotations

from mamba_distributed_tpu.config import ModelConfig

# bf16 peak FLOP/s per chip, keyed by a substring of ``device_kind``
# (Google Cloud TPU documentation; "v5 lite" is how a v5e reports itself).
_PEAK = {
    "v4": 275e12,
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6 lite": 918e12,
    "v6e": 918e12,
}


def peak_flops_per_chip(device) -> float:
    """Peak of ``device``; an unknown ``device_kind`` raises — an MFU
    against some other chip's peak is not a number.  Callers ask only
    when ``device.platform == "tpu"`` and log no MFU otherwise."""
    kind = device.device_kind.lower()
    for key, val in _PEAK.items():
        if key in kind:
            return val
    raise ValueError(
        f"no peak FLOP/s on record for device_kind {device.device_kind!r}; "
        f"known: {sorted(_PEAK)}"
    )


def _mamba2_layer_flops(
    cfg: ModelConfig, seq_len: int, convention: str = "hardware"
) -> float:
    d, di = cfg.d_model, cfg.d_inner
    n, h, p = cfg.effective_d_state, cfg.nheads, cfg.headdim
    g = cfg.ngroups
    l = min(cfg.chunk_size, seq_len)
    f = 2 * d * (2 * di + 2 * g * n + h)  # in_proj
    f += 2 * (di + 2 * g * n) * cfg.d_conv  # depthwise conv
    if convention == "hardware":
        # chunked SSD per token: G Gram matrix is group-shared
        # (ops/ssd.chunk_local), M@x (l*p), chunk states (n*p) and
        # off-diag (n*p) are per-head
        f += 2 * (g * l * n + h * l * p + 2 * h * n * p)
    else:
        # recurrent formulation: B (x) x state update + C . state readout,
        # per head — what the chunked algorithm mathematically computes
        f += 2 * (2 * h * n * p)
    f += 2 * di * d  # out_proj
    return f


def _mamba1_layer_flops(cfg: ModelConfig, seq_len: int) -> float:
    d, di = cfg.d_model, cfg.d_inner
    n, dtr = cfg.effective_d_state, cfg.effective_dt_rank
    f = 2 * d * 2 * di  # in_proj
    f += 2 * di * cfg.d_conv
    f += 2 * di * (dtr + 2 * n)  # x_proj
    f += 2 * dtr * di  # dt_proj
    f += 8 * di * n  # recurrence (dA, dBu, state update, C reduction)
    f += 2 * di * d  # out_proj
    return f


def _attn_layer_flops(cfg: ModelConfig, seq_len: int) -> float:
    nh = cfg.effective_attn_num_heads
    nkv = cfg.effective_attn_num_kv_heads
    hd = cfg.effective_attn_head_dim  # stated where it is not d_model / nh
    f = 2 * cfg.d_model * (nh + 2 * nkv) * hd  # qkv
    f += 2 * seq_len * nh * hd  # scores + AV, causally halved: 4*(t/2)*nh*hd
    f += 2 * nh * hd * cfg.d_model  # out_proj
    return f


def flops_per_token(
    cfg: ModelConfig,
    seq_len: int,
    training: bool = True,
    convention: str = "hardware",
) -> float:
    """Matmul FLOPs per token for one forward (x3 when ``training``).

    ``convention`` is "hardware" (chunked-algorithm FLOPs) or "model"
    (parameter matmuls + recurrent state math only); see module docstring.
    The two differ only for mamba2 layers — mamba1's accounting is already
    the recurrence, and attention's O(t) score/AV terms are model FLOPs.
    """
    if convention not in ("hardware", "model"):
        raise ValueError(f"unknown FLOPs convention {convention!r}")
    total = 0.0
    for i in range(cfg.n_layer):
        mamba, attn = cfg.layer_mixers(i)
        if attn:
            total += _attn_layer_flops(cfg, seq_len)
        if mamba and cfg.ssm_layer == "mamba2":
            total += _mamba2_layer_flops(cfg, seq_len, convention)
        elif mamba:
            total += _mamba1_layer_flops(cfg, seq_len)
        if cfg.d_intermediate > 0:
            mlp = 6 * cfg.d_model * cfg.d_intermediate
            if cfg.moe_num_experts:
                # a token's top_k choices fall on the experts held here in
                # the held share of the router's width (a seed's router is
                # near uniform); dropless, so both conventions count the
                # same products.  The router is whole, and the shared
                # expert is every token's.
                total += mlp * cfg.moe_top_k * (
                    cfg.moe_held[1] / cfg.moe_num_experts)
                total += 2 * cfg.d_model * cfg.moe_num_experts  # router
                total += 6 * cfg.d_model * cfg.moe_shared_intermediate
            else:
                total += mlp
    total += 2 * cfg.d_model * cfg.vocab_size_padded  # LM head
    return total * (3.0 if training else 1.0)
