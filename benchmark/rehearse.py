"""Compile each cell's step programs for a described ``v5e:2x2`` at the real
sizes, without a chip, and print the compiler's memory analysis.

  JAX_PLATFORMS=cpu python benchmark/rehearse.py \
      [--only train|tick|chunk|dp4|weights|reference|gradient] [--config <file>]

What it compiles: the train step at B 8 (one chip) and under a four-device
data mesh; the decode tick at the chat cell's capacity (``mamba2-280m``) and
the chunk step and tick at ``kv_slot_tokens`` 8192 and the long-document
cell's capacity (``hybrid-280m``; at capacity 32 the tick needs 19.3 GiB and
is refused, at 16 it needs 10.1 GiB); and, of the configuration ``--config``
names (``configs/mamba2-280m.json`` unless given; any file of that form, a
scratch one too), making its weights in the dtype it states, one request of
its serving reference, and its training reference's row-block gradient.  Nothing runs, so it says nothing about
results or times: a compile that passes is not a chip run.  The topology is
described inside ``main``, never at import.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)


def _report(name, compiled, t0):
    ma = compiled.memory_analysis()
    gb = lambda x: f"{x / 2**30:.2f} GiB"
    print(f"{name}: compiled in {time.time() - t0:.0f} s; arguments "
          f"{gb(ma.argument_size_in_bytes)}, outputs {gb(ma.output_size_in_bytes)}, "
          f"temporaries {gb(ma.temp_size_in_bytes)}, aliased "
          f"{gb(ma.alias_size_in_bytes)}; peak about "
          f"{gb(ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes - ma.alias_size_in_bytes)}",
          flush=True)


def _sds(tree, sharding):
    import jax

    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)


def train_step(devices, cell_name):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import train as train_cli
    from benchmark import harness
    from mamba_distributed_tpu.models import init_lm_params
    from mamba_distributed_tpu.training.optimizer import make_optimizer
    from mamba_distributed_tpu.training.train_step import make_train_step

    w = harness.load_json(os.path.join(harness.BENCH_DIR, "workloads", cell_name + ".json"))
    old, sys.argv = sys.argv, ["train.py", *w["argv"]]
    try:
        cfg = train_cli.build_config(train_cli.parse_args())
    finally:
        sys.argv = old
    n = cfg.mesh.num_devices
    import numpy as np

    mesh = Mesh(np.asarray(devices[:n]).reshape(cfg.mesh.shape), cfg.mesh.axis_names)
    rep = NamedSharding(mesh, P())
    params = _sds(jax.eval_shape(lambda k: init_lm_params(k, cfg.model),
                                 jax.random.PRNGKey(0)), rep)
    optimizer = make_optimizer(cfg)
    opt = _sds(jax.eval_shape(optimizer.init, params), rep)
    step = make_train_step(cfg, optimizer, mesh, params, opt)
    from mamba_distributed_tpu.parallel.sharding import batch_sharding

    bshard = batch_sharding(mesh)
    ashard = NamedSharding(mesh, P(None, *bshard.spec))
    rows = cfg.micro_batch_size * cfg.data_parallel_size
    x = jax.ShapeDtypeStruct((cfg.grad_accum_steps, rows, cfg.seq_len),
                             jnp.int32, sharding=ashard)
    t0 = time.time()
    compiled = step.lower(params, opt, x, x).compile()
    _report(f"{cell_name} train step ({n} device(s), rows {rows} x "
            f"{cfg.grad_accum_steps})", compiled, t0)
    if n > 1:
        text = compiled.as_text()
        print(f"  collectives in the program: all-reduce {text.count('all-reduce(')}"
              f", all-gather {text.count('all-gather(')}, reduce-scatter "
              f"{text.count('reduce-scatter(')}", flush=True)


def serving(devices, cell_name, what):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark import harness
    from mamba_distributed_tpu.config import get_preset
    from mamba_distributed_tpu.models import init_lm_params
    from mamba_distributed_tpu.serving import engine as engine_mod
    from mamba_distributed_tpu.serving import state_cache
    from mamba_distributed_tpu.serving.prefill import (
        cast_decode_params, prefill_chunk)

    manifest = harness.load_json(os.path.join(CHECKOUT, "BENCHMARK.json"))
    entry = next(x for x in manifest["workloads"] if x["name"] == cell_name)
    w = harness.load_json(os.path.join(harness.BENCH_DIR, "workloads", cell_name + ".json"))
    c = harness.load_json(os.path.join(harness.BENCH_DIR, "configs", entry["config"] + ".json"))
    cfg = dataclasses.replace(get_preset(c["preset"]).model, **c.get("serving", {}))
    one = SingleDeviceSharding(devices[0])
    capacity = w["engine"]["capacity"]
    params = _sds(jax.eval_shape(
        lambda k: cast_decode_params(init_lm_params(k, cfg), cfg=cfg),
        jax.random.PRNGKey(0)), one)
    pool = _sds(jax.eval_shape(lambda: state_cache.init_pool(cfg, capacity, 1)), one)
    hybrid = bool(cfg.attn_layer_idx)
    if what == "tick":
        args = [params, pool]
        if hybrid:
            args += [jax.ShapeDtypeStruct((capacity, cfg.kv_pages_per_slot),
                                          jnp.int32, sharding=one),
                     jax.ShapeDtypeStruct((capacity,), jnp.int32, sharding=one)]
        t0 = time.time()
        compiled = engine_mod._tick.lower(
            *args, cfg=cfg, k_max=50, steps=8, mesh=None, n_micro=None).compile()
        _report(f"{cell_name} decode tick (capacity {capacity})", compiled, t0)
    else:
        chunk = cfg.effective_prefill_chunk_tokens
        state = jax.eval_shape(lambda p: state_cache.read_state(p, 0), pool)
        state = _sds(state, one)
        if hybrid:
            state["attn_blocks"] = pool["state"]["attn_blocks"]
            state["attn_meta"] = (
                jax.ShapeDtypeStruct((1, cfg.kv_pages_per_slot), jnp.int32, sharding=one),
                jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one))
        ids = jax.ShapeDtypeStruct((1, chunk), jnp.int32, sharding=one)
        mask = jax.ShapeDtypeStruct((1, chunk), jnp.float32, sharding=one)
        t0 = time.time()
        compiled = prefill_chunk.lower(params, ids, mask, state, cfg=cfg,
                                       mesh=None).compile()
        _report(f"{cell_name} chunk step ({chunk} tokens)", compiled, t0)


def _config(path):
    """(file, its reference module, its ``model``, its stated weight dtype)."""
    from benchmark import harness, reference

    c = harness.load_json(path)
    return c, reference.of(c), c["model"], reference.params_dtype(c)


def _key(one):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)


def weights(devices, config_path):
    """Making a configuration's weights in the dtype its file states: the
    peak has to read as the finished tree and one layer's float32."""
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    c, ref, m, dtype = _config(config_path)
    one = SingleDeviceSharding(devices[0])
    make = jax.jit(lambda k: ref.init_params(k, m, dtype))
    tree = jax.eval_shape(make, _key(one))
    layer = max((sum(int(np.prod(a.shape[1:])) for a in jax.tree.leaves(g))
                 for name, g in tree.items() if name in ref.STACKED), default=0)
    whole = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    print(f"{c['name']}: {whole / 1e9:.3f} B parameters in {dtype}; the largest "
          f"layer of a stacked group has {layer / 1e9:.3f} B, "
          f"{4 * layer / 2**30:.2f} GiB in float32", flush=True)
    t0 = time.time()
    _report(f"{c['name']} weights in {dtype}", make.lower(_key(one)).compile(), t0)


def reference(devices, config_path, tokens, positions):
    """One request of the serving reference (``served_logits``): ``tokens``
    padded tokens, logits at ``positions`` of them.  The walk is made over
    shapes, each of its programs compiled where it is first called; a layer's
    weights are drawn in one program and read in the next, so the walk's peak
    (its largest program beside what it holds from first layer to last) does
    not grow with the depth."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    c, ref, m, dtype = _config(config_path)
    one = SingleDeviceSharding(devices[0])
    seen = {}

    def jit(fn):
        jitted = jax.jit(fn)

        def call(*args):
            if fn not in seen:
                t0 = time.time()
                seen[fn] = jitted.lower(*args).compile()
                _report(f"  {fn.__name__}", seen[fn], t0)
            return _sds(jax.eval_shape(fn, *args), one)
        return call

    ids = jax.ShapeDtypeStruct((1, tokens), jnp.int32, sharding=one)
    pos = jax.ShapeDtypeStruct((positions,), jnp.int32, sharding=one)
    print(f"{c['name']} serving reference, one request of {tokens} tokens "
          f"({m['n_layer']} layers):", flush=True)
    ref.served_logits(_key(one), m, dtype, ids, pos, "f32", jit=jit)


def gradient(devices, config_path, rows=4):
    """The training reference's gradient of one block of rows."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark.reference import train as ref_train

    c, ref, m, _ = _config(config_path)
    one = SingleDeviceSharding(devices[0])
    params = _sds(jax.eval_shape(lambda k: ref.init_params(k, m, "float32"),
                                 jax.random.PRNGKey(0)), one)
    ids = jax.ShapeDtypeStruct((rows, c["train"]["seq_len"]), jnp.int32, sharding=one)
    t0 = time.time()
    compiled = ref_train._block_grad.lower(
        params, ids, ids, ref, ref_train.freeze(m), "f32").compile()
    _report(f"reference gradient of {rows} rows x {c['train']['seq_len']}",
            compiled, t0)


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--only", default=None)
    p.add_argument("--config", default=os.path.join(
        CHECKOUT, "benchmark", "configs", "mamba2-280m.json"),
        help="the configuration file of the weights, reference and gradient jobs")
    p.add_argument("--tokens", type=int, default=1024,
                   help="the padded length of the reference's one request")
    args = p.parse_args()
    # the program asks jax.default_backend() which attention to take, and sees
    # the CPU here: "0" is its lever for the chip-free TPU lowering (the Pallas
    # kernels through Mosaic, not the interpreter and not the XLA fallback)
    os.environ["MDT_PALLAS_INTERPRET"] = "0"
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    devices = list(topo.devices)
    jobs = {
        "weights": lambda: weights(devices, args.config),
        "reference": lambda: reference(devices, args.config, args.tokens, 64),
        "gradient": lambda: gradient(devices, args.config),
        "train": lambda: train_step(devices, "train-mamba2-280m-1chip"),
        "tick": lambda: serving(devices, "serve-mamba2-280m-chat", "tick"),
        "chunk": lambda: (serving(devices, "serve-hybrid-280m-longdoc", "chunk"),
                          serving(devices, "serve-hybrid-280m-longdoc", "tick")),
        "dp4": lambda: train_step(devices, "train-mamba2-280m-dp4"),
    }
    for name, job in jobs.items():
        if args.only in (None, name):
            job()


if __name__ == "__main__":
    main()
