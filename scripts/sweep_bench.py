"""Sweep train-step configurations on the local chip in one process.

One process, many configs: reuses bench.time_config (the exact
protocol bench.py reports) across ssm_impl / remat / batch-size
combinations and prints one JSON line per configuration, plus a final
{"best": ...} line. Used to pick the defaults bench.py ships with.

  python scripts/sweep_bench.py                 # full sweep
  SWEEP_CONFIGS='[{"B":8,"ssm_impl":"xla"}]' python scripts/sweep_bench.py
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import init_backend, time_config  # noqa: E402

# Round-5 question set. Each row answers a named question from
# VERDICT r4 ("next round" items 1-3); rows are ordered so the
# highest-value answers land first if the run is cut short.
DEFAULT_CONFIGS = [
    # -- MFU ranking: chunk size re-rank post-cumsum_mxu (r4 measured
    #    chunk 512 +7% BEFORE the MXU-ification; re-rank together now)
    {"B": 8, "ssm_impl": "xla", "remat": True, "remat_policy": "all"},
    {"B": 8, "ssm_impl": "xla", "remat": True, "remat_policy": "all",
     "chunk_size": 512},
    {"B": 8, "ssm_impl": "xla", "remat": True, "remat_policy": "all",
     "chunk_size": 1024},
    # -- remat_policy="mixer" (CPU-validated in r4, unmeasured on chip)
    {"B": 8, "ssm_impl": "xla", "remat": True, "remat_policy": "mixer",
     "chunk_size": 512},
    # -- blocked CE alone, then the full combo
    {"B": 8, "ssm_impl": "xla", "remat": True, "remat_policy": "all",
     "loss_impl": "blocked", "chunk_size": 512},
    {"B": 8, "ssm_impl": "xla", "remat": True, "remat_policy": "mixer",
     "loss_impl": "blocked", "chunk_size": 512},
    # -- conv formulation at the candidate combo
    {"B": 8, "ssm_impl": "xla", "remat": True, "remat_policy": "mixer",
     "loss_impl": "blocked", "chunk_size": 512, "conv_impl": "xla_conv"},
    # -- the reference's own batch recipe (ref train.py:43): blocked CE
    #    frees the 3.3 GB logits tensor suspected of the r4 HTTP-500;
    #    the plain row right after names the root cause by contrast
    {"B": 32, "ssm_impl": "xla", "remat": True, "remat_policy": "all",
     "loss_impl": "blocked", "chunk_size": 512},
    {"B": 32, "ssm_impl": "xla", "remat": True, "remat_policy": "all",
     "chunk_size": 512},
    # -- does blocked CE also rescue remat=false (the other r4 compile
    #    failure)?
    {"B": 8, "ssm_impl": "xla", "remat": False,
     "loss_impl": "blocked", "chunk_size": 512},
    # -- batch scaling at the best combo
    {"B": 16, "ssm_impl": "xla", "remat": True, "remat_policy": "mixer",
     "loss_impl": "blocked", "chunk_size": 512},
    # -- Pallas SSD verdict rows (VERDICT item 2: beat XLA or retire) —
    #    round-5 fused fwd/bwd kernels; both chunk sizes since the fused
    #    sequential-chunk grid trades launch count against cell size
    {"B": 8, "ssm_impl": "pallas", "remat": True, "remat_policy": "all",
     "chunk_size": 512},
    {"B": 8, "ssm_impl": "pallas", "remat": True, "remat_policy": "all"},
    # informational: bf16 residual stream (numerics-changing — the
    # reference's residual_in_fp32=True is semantic; this row only
    # quantifies what the fp32 stream costs)
    {"B": 8, "ssm_impl": "xla", "remat": True, "remat_policy": "all",
     "residual_in_fp32": False},
    # hybrid (config-5 architecture, single-chip scale): flash kernel vs
    # blockwise XLA scan on real hardware, at the candidate combo
    # (chunk 512 + mixer remat + blocked CE, matching the row above)
    {"preset": "hybrid-280m", "B": 8, "attn_impl": "pallas",
     "chunk_size": 512, "remat_policy": "mixer", "loss_impl": "blocked"},
    {"preset": "hybrid-280m", "B": 8, "attn_impl": "xla",
     "chunk_size": 512, "remat_policy": "mixer", "loss_impl": "blocked"},
    # Mamba-1 (what the reference's empty ssm_cfg actually builds,
    # SURVEY 2.4): first on-chip ranking of the selective-scan paths
    {"preset": "mamba1-280m", "B": 8, "ssm_impl": "xla"},
    {"preset": "mamba1-280m", "B": 8, "ssm_impl": "pallas"},
]


def main() -> None:
    init_backend()

    configs = (
        json.loads(os.environ["SWEEP_CONFIGS"])
        if os.environ.get("SWEEP_CONFIGS")
        else DEFAULT_CONFIGS
    )
    iters = int(os.environ.get("BENCH_ITERS", "8"))
    results = []
    for spec in configs:
        r = time_config(spec, iters=iters)
        results.append(r)
        print(json.dumps(r), flush=True)
    # "best" picks bench.py's shipped defaults, so only rows of the
    # default (headline) preset compete — hybrid rows are informational
    ok = [r for r in results
          if "tok_per_sec" in r and "preset" not in r]
    if ok:
        best = max(ok, key=lambda r: r["tok_per_sec"])
        print(json.dumps({"best": best}), flush=True)


if __name__ == "__main__":
    main()
