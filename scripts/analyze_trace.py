"""Decompose a jax.profiler trace into op-category time buckets.

Companion to scripts/profile_step.py: point it at the PROFILE_DIR and it
aggregates the device-lane events of the perfetto trace into the buckets
used by docs/KERNELS.md "Round-4 hardware profile" (matmul fusions,
elementwise fusions, copies/reshapes/pads, scan stacking, reduce-window),
plus the top-N individual fusions — the actionable view that drove the
round-4 MXU-ification.

  python scripts/analyze_trace.py chiprun_out/profile [--steps 5] [--top 30]

The trace file is found recursively (plugins/profile/*/.trace.json.gz).
"""

from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import re
import sys

_SKIP = re.compile(r"^(jit_\w+\(\d+\)|while\.\d+|\d+)$")


def find_trace(root: str) -> str:
    if os.path.isfile(root):
        return root
    hits = sorted(
        glob.glob(os.path.join(root, "**", "*.trace.json.gz"), recursive=True)
    )
    if not hits:
        raise SystemExit(f"no *.trace.json.gz under {root!r}")
    return hits[-1]  # newest capture


def categorize(name: str) -> str:
    if "convolution" in name or "dot" in name:
        return "matmul fusions"
    if "dynamic-update-slice" in name or "dynamic-slice" in name:
        return "dyn-slice (scan stacking)"
    if (
        name.startswith(("copy", "reshape", "pad", "transpose"))
        or "copy" in name
        or name.startswith("bitcast")
    ):
        return "copy/reshape/pad"
    if "fusion" in name:
        return "elementwise/reduce fusions"
    if "reduce-window" in name:
        return "reduce-window (cumsum)"
    if "all-reduce" in name or "all-gather" in name or "collective" in name:
        return "collectives"
    return "misc"


def analyze(trace_path: str, steps: int, top: int) -> dict:
    with gzip.open(trace_path) as f:
        tr = json.load(f)
    events = tr["traceEvents"]
    device_pids = {
        e["pid"]
        for e in events
        if e.get("ph") == "M"
        and e.get("name") == "process_name"
        and "TPU" in e["args"].get("name", "")
    }
    # one device lane only: multi-chip traces run the same ops on every
    # lane concurrently, and summing across lanes would report N-chip
    # inflated per-step times
    lane = min(device_pids) if device_pids else None
    agg: collections.Counter = collections.Counter()
    cats: collections.Counter = collections.Counter()
    total = 0.0
    for e in events:
        if e.get("ph") != "X" or "dur" not in e or e.get("pid") != lane:
            continue
        name = e["name"]
        if _SKIP.match(name):
            continue
        total += e["dur"]
        agg[name] += e["dur"]
        cats[categorize(name)] += e["dur"]
    return {
        "trace": trace_path,
        "device_lanes": len(device_pids),
        "steps": steps,
        "total_ms_per_step": round(total / steps / 1e3, 1),
        "categories_ms_per_step": {
            c: round(d / steps / 1e3, 1) for c, d in cats.most_common()
        },
        "top_ops_ms_per_step": {
            n: round(d / steps / 1e3, 2) for n, d in agg.most_common(top)
        },
    }


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("trace_dir")
    p.add_argument("--steps", type=int,
                   default=int(os.environ.get("PROFILE_STEPS", "5")),
                   help="steps captured (divides totals into per-step)")
    p.add_argument("--top", type=int, default=30)
    args = p.parse_args()
    out = analyze(find_trace(args.trace_dir), args.steps, args.top)
    json.dump(out, sys.stdout, indent=1)
    print()


if __name__ == "__main__":
    main()
