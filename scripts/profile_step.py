"""Capture a jax.profiler trace of the train step on the local chip.

Writes a TensorBoard-viewable XLA trace (kernel timeline, HBM traffic,
fusion boundaries) for N steps of the chosen preset — the tool for
attributing step time when chasing the >=45% MFU north star.

  python scripts/profile_step.py                 # 5 traced steps -> ./profile/
  PROFILE_DIR=/tmp/tr BENCH_B=16 python scripts/profile_step.py

Env knobs: PROFILE_DIR (default ./profile), PROFILE_STEPS (default 5),
plus bench.py's BENCH_PRESET/B/T/SSM_IMPL/REMAT/REMAT_POLICY.
The step setup is bench.build_step — exactly what bench.py times.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import _env_spec, _progress, build_step, init_backend  # noqa: E402


def main() -> None:
    init_backend()

    from mamba_distributed_tpu.utils.profiling import trace

    _, step, params, opt_state, x, y = build_step(_env_spec())

    # compile + warm outside the trace
    for _ in range(2):
        params, opt_state, loss, _ = step(params, opt_state, x, y)
    float(loss)
    _progress("warm; tracing...")

    out_dir = os.environ.get("PROFILE_DIR", "profile")
    steps = int(os.environ.get("PROFILE_STEPS", "5"))
    with trace(out_dir):
        for _ in range(steps):
            params, opt_state, loss, _ = step(params, opt_state, x, y)
        float(loss)
    _progress(f"trace written to {out_dir} ({steps} steps)")
    print(f"profile: {os.path.abspath(out_dir)} — open with TensorBoard's "
          "profile plugin")


if __name__ == "__main__":
    main()
