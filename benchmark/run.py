"""Run one cell of the benchmark once.

  python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process; finds the chips the cell asks for or exits non-zero with no
result; makes weights and inputs from ``--seed``; warms the cell's own shapes
(set-up); measures for ``--seconds``; checks what the timed path produced
against ``benchmark/reference``; prints earlier lines freely and, last on
standard output, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (with ``--trace 1`` also ``breakdown``) and, last in
it, ``compared``: each number compared beside its limit.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # before any heavy import: set-up starts here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, manifest: str | None = None,
             data_root: str | None = None, t_process: float | None = None) -> dict:
    """Everything but the command line; returns the result object.
    ``require_tpu=False`` is for the CPU rehearsal tests alone, which print
    no device metric."""
    if CHECKOUT not in sys.path:
        sys.path.insert(0, CHECKOUT)
    from benchmark import harness

    manifest = manifest or os.path.join(CHECKOUT, "BENCHMARK.json")
    data_root = data_root or BENCH_DIR
    cell, devices, kind = harness.open_cell(name, manifest, data_root, require_tpu)
    run = kind.run(cell=cell, seed=int(seed), seconds=float(seconds),
                   trace=bool(trace), devices=devices,
                   t_process=T_PROCESS if t_process is None else t_process)
    summary = None
    # off a TPU (the rehearsal tests) the trace holds no device to reduce
    if trace and run.get("trace_window") is not None \
            and devices[0].platform == "tpu":
        from benchmark import trace_reduce

        tw = run["trace_window"]
        summary = trace_reduce.summarize(
            tw.xplane(), n_devices=len(devices), t_sync=tw.t_sync,
            t0=tw.t_start, t1=tw.t_stop, host_spans=run["spans"].spans)
        run["trace"] = summary
        print(f"trace: busy {summary['busy_s']:.4f} s of {summary['window_s']:.4f} s; "
              f"clock synced {summary['clock_synced']}; seconds by program "
              f"{[[n, round(x, 4)] for n, x in summary['modules'][:8]]}", flush=True)
    line = harness.result_line(cell, run, trace, devices, summary)
    harness.print_compared(run["compared"], run.get("compared_where"))
    return line


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    if CHECKOUT not in sys.path:
        sys.path.insert(0, CHECKOUT)
    from benchmark.harness import Refused

    try:
        line = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except Refused as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
