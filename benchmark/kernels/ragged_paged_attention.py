"""The ragged paged attention kernels: what they have to compute and move.

Decode (one call per attention layer per tick sub-step): every live slot's one
query attends its ``kv_len`` cached keys: 4 * kv_len * heads * head_dim
operations (scores and values) and one read of its K and V pages,
2 * kv_len * kv_heads * head_dim bfloat16 elements.

Prefill (one call per attention layer per chunk): ``chunk`` queries attend the
``kv_len`` keys cached so far, causally inside the chunk:
4 * chunk * (kv_before + (chunk + 1) / 2) * heads * head_dim operations, and
one read of those K and V pages plus the write of the chunk's own.

The roofline time of a call is the larger of operations over the bf16 peak
and bytes over HBM bandwidth; calls add up.
"""

from __future__ import annotations

from benchmark.reference.model import dims


def decode_call(m: dict, kv_lens) -> tuple[float, float]:
    """(operations, bytes) of one decode call over slots with ``kv_lens``."""
    s = dims(m)
    total = float(sum(kv_lens))
    return (4.0 * total * s["anh"] * s["ahd"],
            2.0 * total * s["akv"] * s["ahd"] * 2)


def prefill_call(m: dict, kv_before: int, chunk: int) -> tuple[float, float]:
    """(operations, bytes) of one prefill call of ``chunk`` real tokens."""
    s = dims(m)
    keys = kv_before + (chunk + 1) / 2.0
    ops = 4.0 * chunk * keys * s["anh"] * s["ahd"]
    by = 2.0 * (kv_before + 2 * chunk) * s["akv"] * s["ahd"] * 2
    return ops, by


def roofline_seconds(calls, peaks) -> float:
    return sum(max(o / peaks["flops_bf16"], b / peaks["hbm_bytes_per_s"])
               for o, b in calls)


def least_seconds(run, peaks) -> float | None:
    """Least time for the kernel calls of the traced window, from the
    benchmark's own record of what was resident when."""
    m = run["model"]
    n_attn = len(dims(m)["attn_idx"])
    if not n_attn:
        return None
    t0 = run["trace_window"].t_start
    t1 = run["trace_window"].t_stop
    chunk = run["serving"]["prefill_chunk_tokens"]
    calls = []
    for name, a, b, attrs in run["spans"].within(t0, t1):
        if name == "serving_prefill_chunk":
            # chunk i of a prompt: kv_before = tokens of the chunks before it
            calls += [prefill_call(m, attrs["chunk"] * chunk, chunk)] * n_attn
        elif name == "serving_tick":
            lens = [len(s.prompt) + sum(1 for x in s.times if x < a)
                    for s in run["sent"]
                    if s.times and s.times[0] <= b and s.times[-1] >= a]
            calls += [decode_call(m, lens)] * (n_attn * run["tokens_per_tick"])
    if not calls:
        return None
    return roofline_seconds(calls, peaks)
