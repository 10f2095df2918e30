"""How ``benchmark/tests/data/scoped_trace.xplane.pb`` was recorded (on the
chip, PR 25): a few hundred operations of a small program that holds what the
scope readers have to cope with: a scanned body with two ``jax.named_scope``s
(one under ``jax.checkpoint``), a ``custom_vjp`` whose backward is scoped, a
small named ``pallas_call``, the scan's own stacked write-back, differentiated,
under a ``StepTraceAnnotation`` and a ``TraceAnnotation``.  The scopes take
names of the program's table (``layers``, ``ssd``, ``gate_norm``,
``lm_head_loss``, ``attn_kernel``) so that the readers' tests run on it as
they stand.  It prints every operation's ``op_name`` and the scope it is
filed under, and where the annotations landed.

  chiprun -- python benchmark/tests/record_scoped_trace.py
"""

import glob
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def build():
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def scale_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def scale(x):
        return pl.pallas_call(
            scale_kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            name="scale_kernel")(x)

    @jax.custom_vjp
    def head_loss(h, w):
        with jax.named_scope("lm_head_loss"):
            return jnp.mean(jnp.square(h @ w))

    def fwd(h, w):
        with jax.named_scope("lm_head_loss"):
            return jnp.mean(jnp.square(h @ w)), (h, w)

    def bwd(res, g):
        h, w = res
        with jax.named_scope("lm_head_loss"):
            y = 2.0 * g * (h @ w) / (h.shape[0] * w.shape[1])
            return (y @ w.T).astype(h.dtype), (h.T @ y).astype(w.dtype)

    head_loss.defvjp(fwd, bwd)

    def mix(h, w):
        with jax.named_scope("ssd"):
            return jnp.tanh(h @ w)

    def body(h, w):
        h = jax.checkpoint(mix)(h, w)
        with jax.named_scope("gate_norm"):
            h = h * jax.nn.sigmoid(h.astype(jnp.float32)).astype(h.dtype)
        return h, jnp.sum(h.astype(jnp.float32), axis=0)  # a stacked output

    def loss(ws, head, x):
        with jax.named_scope("layers"):
            h, sums = jax.lax.scan(body, x, ws)
        return head_loss(h, head) + 1e-6 * jnp.mean(sums)

    @jax.jit
    def step(ws, head, x):
        l, (gw, gh) = jax.value_and_grad(loss, argnums=(0, 1))(ws, head, x)
        with jax.named_scope("attn_kernel"):
            gh = scale(gh)
        return l, gw, gh

    x = jnp.ones((256, 256), jnp.bfloat16)
    ws = jnp.ones((4, 256, 256), jnp.bfloat16) * 0.01
    head = jnp.ones((256, 512), jnp.bfloat16) * 0.01
    return step, (ws, head, x)


def main():
    import jax

    from benchmark import trace_reduce, trace_scopes

    out = os.path.join("chiprun_out", "scoped_trace")
    shutil.rmtree(out, ignore_errors=True)
    step, args = build()
    jax.block_until_ready(step(*args))
    jax.profiler.start_trace(out)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench_clock_sync"):
        t_sync = time.perf_counter()
    host_spans = []
    for i in range(3):
        a = time.perf_counter()
        with jax.profiler.StepTraceAnnotation("train", step_num=i):
            with jax.profiler.TraceAnnotation("train_step"):
                jax.block_until_ready(step(*args))
        b = time.perf_counter()
        time.sleep(0.002)
        host_spans += [("train_step", a, b, {}),
                       ("sleep_between_calls", b, time.perf_counter(), {})]
    t1 = time.perf_counter()
    jax.profiler.stop_trace()
    with open(os.path.join("chiprun_out", "scoped_trace.json"), "w") as f:
        json.dump({"t_sync": t_sync, "t0": t0, "t1": t1,
                   "host_spans": host_spans}, f)
    path = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(path, os.path.join("chiprun_out", "scoped_trace.xplane.pb"))
    data = trace_reduce.load(path)
    for plane in data.planes:
        for line in plane.lines:
            ev = [e.name for e in line.events]
            ann = sorted({n for n in ev if n.split("#")[0] in
                          ("train", "train_step", "bench_clock_sync")})
            if ann:
                print("annotations on plane", plane.name, "line",
                      repr(line.name), ann)
    s = trace_reduce.summarize(path, 1, t_sync, t0, t1, host_spans)
    names = trace_scopes.op_names(path)
    rows = sorted(([op, p, sec] for events in s["events"].values() for
                   op, (p, sec) in trace_scopes.attribute(events, names).items()),
                  key=lambda r: -r[2])
    for op, p, sec in rows:
        print(f"{sec * 1e6:9.1f} us  {trace_scopes.scope_of(p):13s} "
              f"{op.split(' = ')[0]:40s} own {names.get(op)} filed {p}")
    print("by scope", {k: v for k, v in trace_scopes.by_scope(rows).items() if v},
          "busy", s["busy_s"], "modules", s["modules"], "idle", s["idle_gaps"],
          "bytes", os.path.getsize(path))


if __name__ == "__main__":
    main()
