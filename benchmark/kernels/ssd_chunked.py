"""The chunked SSD scan of a Mamba-2 block: what it has to compute and move.

One forward call over a sequence of T tokens in chunks of l (nc = T / l), with
h heads of p channels, g groups of B and C, state size n, is four batched
matrix products and a small one (``ops/ssd.py``; 2 operations a
multiply-add):

  G = C B^T per group and chunk            2 l l n    x nc g
  y_diag = (G . L) (dt x) per head, chunk  2 l l p    x nc h
  states = B^T (decay dt x)                2 l n p    x nc h
  y_off = C prev_states                    2 l n p    x nc h
  state passing over the chunks            2 nc nc p n x h

The cumulative sums of dt A (two triangular products of l x l per head and
chunk, 4 l l nc h) are counted too; the element-wise decay arithmetic is not.
It has to read x (T h p), B and C (T g n each) in bfloat16 and dt (T h) in
float32, and write y (T h p) in bfloat16: everything between stays on the
chip in the best case.

The backward of a matrix product is two products of its size, and it reads
the forward's inputs and the cotangent of y and writes the four gradients:
twice the forward's operations and bytes.  Under ``remat`` "all", which the
presets train with, each block's forward runs a second time inside the
backward.  So a training step makes, for every sequence and layer,
``forwards`` 2 and ``backwards`` 1 calls: 4 forwards' worth of operations and
bytes (the metric's file states the two counts).

The roofline time of the calls is the larger of operations over the bf16 peak
and bytes over HBM bandwidth.
"""

from __future__ import annotations

import os

from benchmark import harness
from benchmark.reference.model import dims


def forward_call(m: dict, seq_len: int) -> tuple[float, float]:
    """(operations, bytes) of one forward over one sequence in one layer."""
    s = dims(m)
    h, p, g, n = s["nh"], s["hp"], s["g"], s["n"]
    l = min(m["chunk_size"], seq_len)
    nc = seq_len // l
    ops = (2.0 * l * l * n * nc * g          # G
           + 2.0 * l * l * p * nc * h        # y_diag
           + 2 * 2.0 * l * n * p * nc * h    # states, y_off
           + 2.0 * nc * nc * p * n * h       # state passing
           + 2 * 2.0 * l * l * nc * h)       # the two cumulative sums
    by = seq_len * (2 * h * p * 2 + 2 * g * n * 2 + h * 4)
    return ops, float(by)


def step_calls(m: dict, seq_len: int, forwards: int, backwards: int):
    """(operations, bytes) for one sequence through one layer of a step."""
    ops, by = forward_call(m, seq_len)
    k = forwards + 2 * backwards
    return k * ops, k * by


def bounds(run, peaks, config: str, forwards: int = 2, backwards: int = 1):
    """(seconds by operations, seconds by bytes) of the SSD calls the traced
    window made on one chip, or None where no step ran in it."""
    c = harness.load_json(os.path.join(harness.BENCH_DIR, "configs",
                                       config + ".json"))
    m, seq_len = c["model"], c["train"]["seq_len"]
    tw = run["trace_window"]
    # steps inside the traced window, by the share of each step's span in it
    steps = 0.0
    for _, a, b, _ in run["spans"].within(0.0, float("inf"), "train_step"):
        inside = min(b, tw.t_stop) - max(a, tw.t_start)
        if inside > 0 and b > a:
            steps += inside / (b - a)
    if not steps or not run.get("attempted"):
        return None
    sequences = run["tokens"] / run["attempted"] / run["chips"] / seq_len
    n_mamba = m["n_layer"] - len(m.get("attn_layer_idx", ()))
    ops, by = step_calls(m, seq_len, forwards, backwards)
    calls = steps * sequences * n_mamba
    return (calls * ops / peaks["flops_bf16"],
            calls * by / peaks["hbm_bytes_per_s"])


def least_seconds(run, peaks, config: str, forwards: int = 2,
                  backwards: int = 1) -> float | None:
    found = bounds(run, peaks, config, forwards, backwards)
    if found is None:
        return None
    by_ops, by_bytes = found
    print(f"ssd_chunked: least {max(found):.4f} s for the traced window's "
          f"calls ({by_ops:.4f} s by operations, {by_bytes:.4f} s by bytes: "
          f"bound by {'operations' if by_ops >= by_bytes else 'bytes'})",
          flush=True)
    return max(found)
