"""Find the highest arrival rate an open-loop cell sustains: one process, the
cell's own mix at each of a list of rates, the backlog at the window's close
beside the tails.  Run once, when a cell is defined; the rate chosen (about
four fifths of the highest without a growing backlog) is then written into
the cell's file as a number.

  python3 benchmark/sweep.py --workload <cell> --rates 20,30,40 --seconds 20 --seed 1
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T0 = time.perf_counter()
CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--manifest", default=os.path.join(CHECKOUT, "BENCHMARK.json"),
                   help="a manifest that lists the cell: a candidate cell is "
                        "read here before BENCHMARK.json takes it")
    args = p.parse_args()
    from benchmark import harness

    cell, devices, kind = harness.open_cell(args.workload, args.manifest,
                                            harness.BENCH_DIR, require_tpu=True)
    cell.workload["check_requests"] = 1
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        cell.workload["mix"]["arrivals"]["rate_per_s"] = rate
        run = kind.run(cell=cell, seed=args.seed + i, seconds=args.seconds,
                       trace=False, devices=devices, t_process=T0)
        e = run["end_to_end"]
        print("SWEEP " + json.dumps({
            "rate_per_s": rate, "sent": run["attempted"],
            "finished": len(run["finished"]),
            "backlog_at_close": run["backlog_at_close"],
            "ttft_p95_ms": e["ttft_p95_ms"], "itl_p95_ms": e["itl_p95_ms"],
            "tokens_per_s": e["serve_tokens_per_s"],
            "correct": run["correct"], "compared": run["compared"]}), flush=True)
        run = None
        harness.release()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
