"""The decode tick over the slot pool: what it has to move.

One tick advances every live slot by ``steps`` tokens.  Each sub-step has to
read the weights once (bfloat16) and, for every live slot, read and write its
recurrent state: per Mamba-2 layer the float32 SSM state (nheads x headdim x
d_state) and the bfloat16 convolution window ((d_conv - 1) x conv_dim).  The
tick is bound by memory bandwidth (a few operations per byte), so its roofline
is bytes over the chip's HBM bandwidth.  Attention layers' KV reads are the
ragged paged kernel's own account (``ragged_paged_attention.py``) and are left
out here, which only lowers this share.
"""

from __future__ import annotations

from benchmark.reference.model import dims


def weight_bytes(m: dict) -> int:
    """bfloat16 bytes of every matrix a decode step reads: the blocks'
    projections, the convolutions and the tied head (the embedding's one row
    per token is negligible)."""
    s = dims(m)
    n_attn = len(s["attn_idx"])
    per_mamba = (s["d"] * s["d_in_proj"] + s["di"] * s["d"]
                 + s["conv_dim"] * s["w"])
    total = (s["n_layer"] - n_attn) * per_mamba
    if n_attn:
        total += n_attn * (s["d"] * (s["anh"] + 2 * s["akv"]) * s["ahd"]
                           + s["anh"] * s["ahd"] * s["d"])
    total += s["vocab"] * s["d"]  # head
    return 2 * total


def state_bytes_per_slot(m: dict) -> int:
    """One slot's recurrent state over all Mamba-2 layers."""
    s = dims(m)
    n_mamba = s["n_layer"] - len(s["attn_idx"])
    ssm = s["nh"] * s["hp"] * s["n"] * 4
    conv = (s["w"] - 1) * s["conv_dim"] * 2
    return n_mamba * (ssm + conv)


def tick_bytes(m: dict, live_slots: float, steps: int) -> float:
    return steps * (weight_bytes(m) + 2 * live_slots * state_bytes_per_slot(m))


def least_seconds(run, peaks) -> float | None:
    """Least time for the ticks the traced window launched."""
    t0 = run["trace_window"].t_start
    t1 = run["trace_window"].t_stop
    ticks = run["spans"].within(t0, t1, "serving_tick")
    if not ticks:
        return None
    total = sum(tick_bytes(run["model"], a.get("occupied", run["capacity"]),
                           run["tokens_per_tick"]) for _, _, _, a in ticks)
    return total / peaks["hbm_bytes_per_s"]
