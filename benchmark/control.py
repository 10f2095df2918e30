"""Read the program and the lower-precision control of a cell's ``correct``
on several seeds in one process (on the chip, at the cell's own size).

  python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --seconds 10 \
      [--control fp8] [--faults half,quarter,frozen]

Serving cells: a short window at the cell's own load; the program's widest
logit gap and, over the same prompts and served tokens, the gap of the token
the control precision puts first.  Training cells need no window: the
program's numbers come from its first steps; the control is the reference in
the lower precision put in the program's place; the faults are planted in the
reference put in the program's place (half or a quarter of the rows kept, the
mean taken over them; the state left unchanged).

The program, the control and every fault go through the same
``harness.judge`` with the cell's own limits, and each line says how it was
judged: ``correct`` has to read true for the program and false for the rest.
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


def train_controls(cell, seed, precision, faults, devices):
    """The control and the faults of a training cell, each compared with the
    float32 reference as the program is."""
    from benchmark import harness, reference
    from benchmark.reference import train as ref_train
    from benchmark.traffic import tokens as traffic_tokens

    w, m, t = cell.workload, cell.config["model"], cell.config["train"]
    data_dir = os.path.join(harness.SCRATCH, "data", cell.name)
    shard = os.path.join(data_dir, "bench_train_000000.npy")  # the run's own
    argv = w["argv"]
    rows = t["micro_batch_size"]  # the configuration as it is run
    if "--mesh-data" in argv:
        rows *= int(argv[argv.index("--mesh-data") + 1])
    batches = traffic_tokens.step_batches(shard, int(w["warm_steps"]),
                                          t["grad_accum_steps"], rows, t["seq_len"])
    mod, dtype = reference.of(cell.config), reference.params_dtype(cell.config)
    params0 = ref_train.initial_params(mod, seed, m, dtype)
    rb = int(w.get("reference_row_block", 4))
    ref = ref_train.first_steps(mod, params0, m, t, batches, row_block=rb,
                                devices=devices)
    n = rows * t["grad_accum_steps"]
    out = {}
    jobs = {}
    if precision:
        jobs["control_" + precision] = dict(precision=precision)
    for f in faults:
        if f == "half":
            jobs["fault_half_batch"] = dict(rows=slice(0, n // 2))
        elif f == "quarter":
            jobs["fault_no_exchange"] = dict(rows=slice(0, n // 4))
        elif f == "frozen":
            jobs["fault_state_unchanged"] = dict(frozen=True)
    for name, kw in jobs.items():
        got = ref_train.first_steps(mod, params0, m, t, batches, row_block=rb,
                                    devices=devices, **kw)
        vals = ref_train.compare(got, ref)
        vals.pop("_where")
        out[name] = judged(vals, w["limits"])
    return out


def judged(values: dict, limits: dict) -> dict:
    """The verdict of the run's own ``judge`` on these numbers: what a run
    that produced them would print as ``correct``, and which numbers fail.
    Numbers the limits name and ``values`` lacks (``window_compiles`` of a
    reference that ran no window) are not held against it."""
    from benchmark import harness

    ok, compared = harness.judge(
        values, {k: v for k, v in limits.items() if k in values})
    return {"correct": ok,
            "failed": [k for k, c in compared.items()
                       if not (c["value"] is not None and c["value"] <= c["limit"])],
            "compared": compared}


def read_seed(cell, kind, devices, seed, seconds, control, faults) -> dict:
    """One seed: the program, then the control and the faults, each judged."""
    from benchmark import harness

    if cell.workload["kind"] == "train":
        run = kind.run(cell=cell, seed=seed, seconds=seconds, trace=False,
                       devices=devices, t_process=T0)
        rec = {"seed": seed, "program": {"correct": run["correct"],
                                         "compared": run["compared"]}}
        run = None
        harness.release()
        if control or faults:
            rec.update(train_controls(cell, seed, control, faults, devices))
        return rec
    run = kind.run(cell=cell, seed=seed, seconds=seconds, trace=False,
                   devices=devices, t_process=T0, control=control)
    rec = {"seed": seed, "attempted": run["attempted"],
           "program": {"correct": run["correct"], "compared": run["compared"]}}
    if control:
        values = {k: c["value"] for k, c in run["compared"].items()}
        values["logit_gap"] = run["control_gap"]
        rec["control_" + control] = judged(values, cell.workload["limits"])
    return rec


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--control", default=None)
    p.add_argument("--faults", default="")
    p.add_argument("--manifest", default=os.path.join(CHECKOUT, "BENCHMARK.json"),
                   help="a manifest that lists the cell: a candidate cell is "
                        "read here before BENCHMARK.json takes it")
    p.add_argument("--data-root", default=None,
                   help="where that manifest's configs/ and workloads/ are "
                        "(default: benchmark/)")
    args = p.parse_args()
    from benchmark import harness

    cell, devices, kind = harness.open_cell(
        args.workload, args.manifest, args.data_root or harness.BENCH_DIR,
        require_tpu=True)
    faults = [f for f in args.faults.split(",") if f]
    for seed in (int(s) for s in args.seeds.split(",")):
        rec = read_seed(cell, kind, devices, seed, args.seconds, args.control,
                        faults)
        print("CONTROL " + json.dumps(rec), flush=True)
        print("VERDICT seed %d: " % seed + "; ".join(
            f"{k} correct={v['correct']}" + (f" failed={v['failed']}"
                                             if v.get("failed") else "")
            for k, v in rec.items() if isinstance(v, dict)), flush=True)
        harness.release()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
