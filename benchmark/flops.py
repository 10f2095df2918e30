"""Operations a model needs per token, "model" convention.

Counts what the model defines, whatever implements it: 2*m*n per (m x n)
parameter matrix per token, the recurrent form of the state-space layer (state
update and readout, no chunk-size term), attention's scores and values causally
halved.  A training step is three forwards (forward + two in the backward);
recomputed operations do not count.  Copied from the program's
``utils/flops.py`` ("model" convention) so that the program cannot move the
yardstick; ``benchmark/tests`` pins the two equal on today's configurations.
"""

from __future__ import annotations

from benchmark.reference.model import dims


def _mamba2_layer(m: dict) -> float:
    s = dims(m)
    f = 2 * s["d"] * s["d_in_proj"]  # in_proj
    f += 2 * s["conv_dim"] * s["w"]  # depthwise conv
    f += 2 * (2 * s["nh"] * s["n"] * s["hp"])  # state update + readout
    f += 2 * s["di"] * s["d"]  # out_proj
    return f


def _attn_layer(m: dict, context: float) -> float:
    s = dims(m)
    nh, nkv, hd = s["anh"], s["akv"], s["ahd"]
    f = 2 * s["d"] * (nh + 2 * nkv) * hd  # qkv
    f += 4 * context * nh * hd  # scores + values over ``context`` keys
    f += 2 * nh * hd * s["d"]  # out_proj
    return f


def forward_flops_per_token(m: dict, context: float) -> float:
    """One forward, for a token that attends ``context`` keys (for a whole
    causal sequence of length T the mean is T/2)."""
    s = dims(m)
    n_attn = len(s["attn_idx"])
    total = (s["n_layer"] - n_attn) * _mamba2_layer(m)
    if n_attn:
        total += n_attn * _attn_layer(m, context)
    return total + 2 * s["d"] * s["vocab"]  # LM head


def train_flops_per_token(m: dict, seq_len: int) -> float:
    return 3.0 * forward_flops_per_token(m, seq_len / 2)
