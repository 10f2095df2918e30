"""Kind ``train``: the program's ``Trainer.run`` under ``train.py``'s own
construction, measured for a fixed time.

Set-up builds one ``Trainer`` (``train.build_config`` on the cell's ``argv``),
hands it the benchmark's weights and shards made from ``--seed``, and drives it
through step-0 validation and the first ``warm_steps`` steps through the loop's
own call and feed.  The same object, the same ``run()`` call, then enters the
window: the hook on the loop's ``data_load`` span notes each step boundary and
closes the window at the first boundary past ``--seconds``.

``correct`` compares those first steps (losses; the first clipped gradient
read from Adam's first moment after one step; the parameters' change after
``warm_steps``) with the configuration's reference (``benchmark.reference.of``)
on the same weights and rows, once the window has closed and the trainer is
freed.
"""

from __future__ import annotations

import json
import os
import sys
import time

from benchmark import harness, reference
from benchmark.reference import train as ref_train
from benchmark.traffic import tokens as traffic_tokens

# the program modules whose TRACE_COUNTS a training window watches, where the
# configuration's file names no others (``trace_count_modules``)
TRACE_COUNT_MODULES = ("mamba_distributed_tpu.training.train_step",)


class WindowClosed(Exception):
    pass


def _find_mu(opt_state):
    """Adam's first moment inside an optax chain's state."""
    if hasattr(opt_state, "mu"):
        return opt_state.mu
    if isinstance(opt_state, (tuple, list)):
        for s in opt_state:
            found = _find_mu(s)
            if found is not None:
                return found
    return None


def init_of(cell):
    """``key -> the configuration's weights``, in the dtype its file states."""
    ref, dtype = reference.of(cell.config), reference.params_dtype(cell.config)
    m = cell.config["model"]
    return lambda k: ref.init_params(k, m, dtype)


def build_trainer(cell, seed, data_dir, log_dir, devices):
    import jax

    import train as train_cli
    from mamba_distributed_tpu.training import Trainer

    argv = ["train.py", *cell.workload["argv"], "--data-dir", data_dir,
            "--log-dir", log_dir, "--seed", str(seed & 0x7FFFFFFF)]
    old = sys.argv
    sys.argv = argv
    try:
        cfg = train_cli.build_config(train_cli.parse_args())
    finally:
        sys.argv = old
    m, t = cell.config["model"], cell.config["train"]
    harness.check_config(m, cfg.model, cell.name)
    harness.check_config(t, cfg, cell.name)
    trainer = Trainer(cfg, devices=devices)
    # the benchmark's weights, in the trainer's own layout and placement
    init = init_of(cell)
    want = jax.eval_shape(init, jax.random.PRNGKey(0))
    have = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        trainer.params)
    if jax.tree.structure(want) != jax.tree.structure(have) or any(
            a.shape != b.shape or a.dtype != b.dtype for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(have))):
        raise harness.Refused(f"{cell.name}: the program's parameter tree is "
                              f"not the one the configuration's reference makes")
    pshard = jax.tree.map(lambda a: a.sharding, trainer.params)
    trainer.params = None
    trainer.params = jax.jit(init, out_shardings=pshard)(reference.seed_key(seed))
    return trainer, cfg


def run(cell, seed, seconds, trace, devices, t_process):
    import jax
    import jax.numpy as jnp

    w, m, t = cell.workload, cell.config["model"], cell.config["train"]
    warm = int(w["warm_steps"])
    data_dir = os.path.join(harness.SCRATCH, "data", cell.name)
    log_dir = os.path.join(harness.SCRATCH, "log", cell.name)
    shards = traffic_tokens.write_shards(
        data_dir, seed, m["vocab_size"], int(w["train_shard_tokens"]),
        int(w["val_shard_tokens"]))
    ref, init = reference.of(cell.config), init_of(cell)
    counters = lambda: harness.trace_counts(
        cell.config.get("trace_count_modules", TRACE_COUNT_MODULES))
    trainer, cfg = build_trainer(cell, seed, data_dir, log_dir, devices)
    rows = cfg.micro_batch_size * cfg.data_parallel_size
    accum = cfg.grad_accum_steps
    b1 = t["adam_b1"]
    grad_of_mu = jax.jit(lambda mu: ref_train.leaf_norms(
        jax.tree.map(lambda x: x / (1 - b1), mu), ref.STACKED))
    delta_of = jax.jit(lambda p, k: ref_train.leaf_norms(
        jax.tree.map(jnp.subtract, p, init(k)), ref.STACKED))
    tw = harness.TraceWindow.of(cell, trace)
    st = {"bounds": [], "grad": None, "delta": None, "t0": None, "t1": None,
          "traces0": None}

    def on_span(name, attrs):
        if name != "data_load":
            return
        k, now = attrs["step"], time.perf_counter()
        if k == 1:
            st["grad"] = grad_of_mu(_find_mu(trainer.opt_state))
        if k == warm:
            st["delta"] = jax.block_until_ready(
                delta_of(trainer.params, reference.seed_key(seed)))
            st["traces0"] = counters()
            st["t0"] = time.perf_counter()
            return
        if k > warm:
            st["bounds"].append(now)
            left = seconds - (now - st["t0"])
            if left <= 0:
                st["t1"] = now
                raise WindowClosed
            if tw is not None and not tw.started and left <= tw.seconds:
                tw.start()

    spans = harness.SpanRecorder(on_span)
    watch = harness.CompileWatch()
    trainer.tracer = spans
    try:
        trainer.run()
    except WindowClosed:
        pass
    finally:
        trainer.finish()
    if tw is not None:
        tw.stop(st["t1"])
    t0, t1 = st["t0"], st["t1"]
    steps = len(st["bounds"])
    print(f"window: {steps} steps of {cfg.total_batch_size} tokens in "
          f"{t1 - t0:.3f} s", flush=True)
    traces1 = counters()
    window_compiles = watch.report(
        t0, t1, {k: traces1[k] - v for k, v in st["traces0"].items()})
    watch.close()
    peak = harness.memory_peak_bytes(devices)
    print(f"memory_peak_bytes {peak} (as the backend reports it)", flush=True)

    prog = {"grad": ref_train.flat_norms(st["grad"]),
            "delta": ref_train.flat_norms(st["delta"])}
    with open(os.path.join(log_dir, "metrics.jsonl")) as f:
        records = [json.loads(line) for line in f]
    prog["losses"] = [r["loss"] for r in records if r["kind"] == "train"][:warm]
    for loader in (trainer.train_loader, trainer.val_loader):
        loader.close()
    del trainer
    st["grad"] = st["delta"] = None
    harness.release()

    t_ref = time.perf_counter()
    params0 = ref_train.initial_params(
        ref, seed, m, reference.params_dtype(cell.config))
    batches = traffic_tokens.step_batches(shards["train"], warm, accum, rows,
                                          t["seq_len"])
    steps_ref = ref_train.first_steps(
        ref, params0, m, t, batches,
        row_block=int(w.get("reference_row_block", 4)), devices=devices)
    values = ref_train.compare(prog, steps_ref)
    where = values.pop("_where")
    values["window_compiles"] = float(window_compiles)
    ok, compared = harness.judge(values, w["limits"])
    print(f"reference: {warm} steps in {time.perf_counter() - t_ref:.1f} s; "
          f"losses program {prog['losses']} reference {steps_ref['losses']}; "
          f"first grad norm reference {steps_ref['grad_norm']:.4f}", flush=True)

    tokens = steps * cfg.total_batch_size
    return {
        "correct": ok and steps > 0, "compared": compared, "compared_where": where,
        "attempted": steps, "failed": 0,
        "end_to_end": {"train_tokens_per_s": tokens / (t1 - t0),
                       "setup_s": t0 - t_process},
        "memory_peak_bytes": peak, "spans": spans, "trace_window": tw,
        "window": (t0, t1), "tokens": tokens, "chips": len(devices),
        "device_kind": devices[0].device_kind, "platform": devices[0].platform,
        "model_flops": tokens * ref.train_flops_per_token(m, t["seq_len"]),
    }
