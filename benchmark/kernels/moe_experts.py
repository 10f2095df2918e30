"""The routed experts of a decode tick: what they have to compute and move.

One launch (a ``serving_tick``) runs ``steps`` sub-steps through every layer's
expert layer.  A row routed to a held expert costs that expert's three
matrices, 6 x d_model x d_intermediate operations (up, gate, down), and an
expert that any row reached has to be read once in that sub-step and layer:
3 x d_model x d_intermediate bfloat16 weights.  Beside the weights a row's
input and output move (d_model bfloat16 in, d_model float32 out).  The count
is of the work the model defines, the same whatever implements it: an expert
no row reached is not counted, though a form that runs every held expert over
every lane reads it.

The program counts both on the device and sets them on the launch's span:
``expert_rows`` (rows routed to held experts, summed over the launch's
sub-steps and layers, live lanes alone) and ``expert_hits`` (the held experts
reached, summed likewise: never more than held x steps x layers).  A launch's
least time is the larger of its operations over the bf16 peak and its bytes
over HBM bandwidth (the larger of the sums, which is not over the sum of the
sub-steps' larger); launches add up.
"""

from __future__ import annotations


def tick_call(m: dict, expert_rows: float, expert_hits: float) -> tuple:
    """(operations, bytes) of one launch's routed products."""
    d, ff = m["d_model"], m["d_intermediate"]
    ops = 6.0 * d * ff * expert_rows
    by = expert_hits * 3 * d * ff * 2 + expert_rows * d * (2 + 4)
    return ops, float(by)


def calls(run) -> list:
    t0, t1 = run["trace_window"].t_start, run["trace_window"].t_stop
    return [tick_call(run["model"], a["expert_rows"], a["expert_hits"])
            for _, _, _, a in run["spans"].within(t0, t1, "serving_tick")
            if a.get("expert_rows") is not None
            and a.get("expert_hits") is not None]


def least_seconds(run, peaks) -> float | None:
    found = calls(run)
    if not found:
        return None
    least = sum(max(o / peaks["flops_bf16"], b / peaks["hbm_bytes_per_s"])
                for o, b in found)
    print(f"moe_experts: least {least:.4f} s for the traced window's "
          f"{len(found)} ticks ({sum(o for o, _ in found) / peaks['flops_bf16']:.4f} s "
          f"by operations, {sum(b for _, b in found) / peaks['hbm_bytes_per_s']:.4f} s "
          f"by bytes)", flush=True)
    return least
