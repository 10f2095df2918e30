"""How ``benchmark/tests/data/small_trace.xplane.pb`` was recorded (on the
chip, PR 24): a few hundred operations of a small scanned program, with the
clock annotation the harness uses.  Run through the chip tool; the trace comes
back under ``chiprun_out/`` with ``small_trace.json``, the run's own clock\nreadings; both are kept in ``benchmark/tests/data/``.

  chiprun -- python benchmark/tests/record_trace.py
"""

import glob
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main():
    import jax
    import jax.numpy as jnp

    from benchmark import trace_reduce

    out = os.path.join("chiprun_out", "small_trace")
    shutil.rmtree(out, ignore_errors=True)

    @jax.jit
    def step(x, ws):
        def body(h, w):
            return jnp.tanh(h @ w), None
        return jax.lax.scan(body, x, ws)[0]

    x = jnp.ones((256, 256), jnp.bfloat16)
    ws = jnp.ones((4, 256, 256), jnp.bfloat16) * 0.01
    jax.block_until_ready(step(x, ws))
    jax.profiler.start_trace(out)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench_clock_sync"):
        t_sync = time.perf_counter()
    host_spans = []
    for _ in range(3):
        a = time.perf_counter()
        jax.block_until_ready(step(x, ws))
        b = time.perf_counter()
        time.sleep(0.002)
        host_spans += [("call", a, b, {}),
                       ("sleep_between_calls", b, time.perf_counter(), {})]
    t1 = time.perf_counter()
    jax.profiler.stop_trace()
    with open(os.path.join("chiprun_out", "small_trace.json"), "w") as f:
        json.dump({"t_sync": t_sync, "t0": t0, "t1": t1,
                   "host_spans": host_spans}, f)
    path = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(path, os.path.join("chiprun_out", "small_trace.xplane.pb"))
    data = trace_reduce.load(path)
    for plane in data.planes:
        print("plane", plane.name)
        for line in plane.lines:
            ev = list(line.events)
            print("  line", repr(line.name), len(ev),
                  [e.name for e in ev[:6]])
    s = trace_reduce.summarize(path, 1, t_sync, t0, t1, host_spans)
    s.pop("events")
    print(s, "bytes", os.path.getsize(path), "t", t1 - t0)


if __name__ == "__main__":
    main()
