"""Benchmark: time the jitted 280M train step on the local accelerator.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
Baseline: the reference's derived ~174K tokens/sec/GPU on 8xA100
(BASELINE.md "Aggregate throughput"); vs_baseline = ours / 174000.  It is
a throughput-per-chip comparison at the same model + seq_len (each side
runs its own batch size — the reference used B=32/GPU), and the key is
omitted entirely for other presets/seq_lens, which have no reference
number to compare against.

Progress goes to stderr with timestamps so a hung run is diagnosable from
the log tail.  A number printed here was measured on a TPU in this run:
with no TPU, or a malformed variable, the exit is non-zero and nothing is
printed on stdout.

Env knobs (for sweeps; defaults are the shipped configuration):
  BENCH_PRESET     preset name            (default mamba2-280m)
  BENCH_B          micro batch size       (default 8)
  BENCH_T          sequence length        (default 1024)
  BENCH_SSM_IMPL   xla | pallas           (default preset's)
  BENCH_REMAT      0 | 1                  (default preset's)
  BENCH_REMAT_POLICY all | dots | mixer   (default preset's)
  BENCH_CHUNK_SIZE SSD chunk length       (default preset's)
  BENCH_ITERS      timed iterations       (default 10)
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

_T0 = time.time()

# reference-derived tokens/sec/GPU for the 280M @ T=1024 recipe (BASELINE.md)
BASELINE_TOK_PER_SEC = 174_000.0
BASELINE_PRESET = "mamba2-280m"
BASELINE_T = 1024

# shipped single-chip defaults (shared by time_config and _env_spec)
DEFAULT_B = 8

# ModelConfig fields a bench/sweep spec may override (single source of
# truth for build_step, time_config, and the sweep-matrix validity test)
MODEL_SPEC_KEYS = ("ssm_impl", "attn_impl", "remat", "remat_policy",
                   "chunk_size", "loss_impl", "conv_impl",
                   "residual_in_fp32")
DEFAULT_T = BASELINE_T
DEFAULT_PRESET = BASELINE_PRESET


def _progress(msg: str) -> None:
    print(f"[bench +{time.time() - _T0:6.1f}s] {msg}", file=sys.stderr, flush=True)


def _metric_name(preset: str) -> str:
    return f"train_tokens_per_sec_per_chip_{preset.replace('-', '_')}"


def init_backend():
    """Place the compile cache, initialize the backend and insist on a TPU.

    Shared by bench.py, scripts/sweep_bench.py and scripts/tpu_smoke.py
    so their backends can never diverge.  These are chip tools: on any other platform they
    exit non-zero before printing anything on stdout.
    """
    import jax

    from mamba_distributed_tpu.utils.platform import configure_compile_cache

    configure_compile_cache()
    _progress(f"jax {jax.__version__} imported; initializing backend...")
    dev = jax.devices()[0]
    _progress(f"backend up: {len(jax.devices())}x {dev.device_kind}")
    if dev.platform != "tpu":
        raise SystemExit(
            f"needs a TPU: jax.devices()[0].platform is {dev.platform!r}"
        )
    return dev


def build_step(spec: dict):
    """Build the single-chip jitted train step for one configuration.

    Returns (cfg, step, params, opt_state, x, y) with x/y carrying the
    (1, B, T) accum axis.
    """
    import jax
    import jax.numpy as jnp

    from mamba_distributed_tpu.config import get_preset
    from mamba_distributed_tpu.models import init_lm_params
    from mamba_distributed_tpu.parallel.mesh import build_mesh
    from mamba_distributed_tpu.parallel.sharding import (
        opt_state_shardings,
        param_shardings,
    )
    from mamba_distributed_tpu.training.optimizer import make_optimizer
    from mamba_distributed_tpu.training.train_step import make_train_step

    B = spec.get("B", DEFAULT_B)
    T = spec.get("T", DEFAULT_T)
    preset = spec.get("preset", DEFAULT_PRESET)
    cfg = get_preset(preset, micro_batch_size=B, seq_len=T, total_batch_size=B * T)
    model_over = {k: spec[k] for k in MODEL_SPEC_KEYS if k in spec}
    if model_over:
        cfg = dataclasses.replace(
            cfg, model=dataclasses.replace(cfg.model, **model_over)
        )
    mesh = build_mesh(cfg.mesh, jax.devices()[:1])

    key = jax.random.PRNGKey(0)
    shapes = jax.eval_shape(lambda k: init_lm_params(k, cfg.model), key)
    pshard = param_shardings(shapes, mesh, False)
    params = jax.jit(
        lambda k: init_lm_params(k, cfg.model), out_shardings=pshard
    )(key)
    jax.block_until_ready(params)
    _progress(f"{spec or 'default'}: params initialized on device")
    optimizer = make_optimizer(cfg)
    opt_shapes = jax.eval_shape(optimizer.init, params)
    oshard = opt_state_shardings(opt_shapes, shapes, pshard, mesh)
    opt_state = jax.jit(optimizer.init, out_shardings=oshard)(params)
    step = make_train_step(cfg, optimizer, mesh, params, opt_state)

    kx, ky = jax.random.split(jax.random.PRNGKey(1))
    x = jax.device_put(
        jax.random.randint(kx, (1, B, T), 0, cfg.model.vocab_size, jnp.int32)
    )
    y = jax.device_put(
        jax.random.randint(ky, (1, B, T), 0, cfg.model.vocab_size, jnp.int32)
    )
    return cfg, step, params, opt_state, x, y


def time_config(spec: dict, iters: int = 10) -> dict:
    """Time the jitted train step for one configuration on the local chip.

    spec keys (all optional): preset, B, T, ssm_impl, attn_impl, remat,
    remat_policy, chunk_size.
    Returns {**spec, tok_per_sec, mfu, step_ms} or {**spec, error} on
    failure (e.g. OOM at large batch) so sweeps can continue.  Unknown
    spec keys raise immediately — a typo in a sweep config is a bug, not
    a data point.
    """
    from mamba_distributed_tpu.utils.flops import flops_per_token, peak_flops_per_chip

    known = {"preset", "B", "T", *MODEL_SPEC_KEYS}
    unknown = set(spec) - known
    if unknown:
        raise KeyError(
            f"unknown bench spec keys {sorted(unknown)}; known: {sorted(known)}"
        )

    try:
        cfg, step, params, opt_state, x, y = build_step(spec)
        B, T = cfg.micro_batch_size, cfg.seq_len
        # warmup (compile + 2 steps); float() waits for the step
        for i in range(3):
            params, opt_state, loss, _ = step(params, opt_state, x, y)
            if i == 0:
                float(loss)
                _progress("train step compiled + first step done")
        float(loss)

        t0 = time.time()
        for _ in range(iters):
            params, opt_state, loss, _ = step(params, opt_state, x, y)
        final_loss = float(loss)  # steps chain on params; closes all iters
        dt = (time.time() - t0) / iters
    except Exception as e:  # e.g. OOM at larger B — report and let sweeps go on
        return {**spec, "error": f"{type(e).__name__}: {str(e)[:200]}"}

    import jax

    tok_per_sec = B * T / dt
    peak = peak_flops_per_chip(jax.devices()[0])
    fpt_hw = flops_per_token(cfg.model, T, training=True, convention="hardware")
    fpt_model = flops_per_token(cfg.model, T, training=True, convention="model")
    return {
        **spec,
        "tok_per_sec": round(tok_per_sec, 1),
        # the >=45% target is judged on mfu_model, the stricter convention
        "mfu_model": round(fpt_model * tok_per_sec / peak, 4),
        "mfu_hw": round(fpt_hw * tok_per_sec / peak, 4),
        "step_ms": round(dt * 1000, 2),
        "loss": round(final_loss, 4),
        "ssm_impl": cfg.model.ssm_impl,
        "remat": cfg.model.remat,
    }


def _env_spec() -> dict:
    spec = {
        "B": int(os.environ.get("BENCH_B", str(DEFAULT_B))),
        "T": int(os.environ.get("BENCH_T", str(DEFAULT_T))),
        "preset": os.environ.get("BENCH_PRESET", DEFAULT_PRESET),
    }
    if os.environ.get("BENCH_SSM_IMPL"):
        spec["ssm_impl"] = os.environ["BENCH_SSM_IMPL"]
    if os.environ.get("BENCH_REMAT"):
        v = os.environ["BENCH_REMAT"]
        if v not in ("0", "1"):
            raise SystemExit(f"BENCH_REMAT must be 0 or 1, got {v!r}")
        spec["remat"] = v == "1"
    if os.environ.get("BENCH_REMAT_POLICY"):
        spec["remat_policy"] = os.environ["BENCH_REMAT_POLICY"]
    if os.environ.get("BENCH_CHUNK_SIZE"):
        spec["chunk_size"] = int(os.environ["BENCH_CHUNK_SIZE"])
    return spec


def main() -> None:
    # env parsing first: a malformed variable is an operator error and
    # must be reported before the backend comes up
    try:
        spec = _env_spec()
        iters = int(os.environ.get("BENCH_ITERS", "10"))
    except ValueError as e:
        raise SystemExit(f"bad_env_spec: {e}")
    dev = init_backend()
    r = time_config(spec, iters=iters)
    if "error" in r:
        # on-chip per-config failure (e.g. OOM): echo the spec for
        # attribution, like sweep rows do
        print(json.dumps({"value": None, "device": dev.device_kind, **r}),
              flush=True)
        raise SystemExit(1)

    out = {
        "metric": _metric_name(spec["preset"]),
        "value": r["tok_per_sec"],
        "unit": "tokens/sec/chip",
        # two conventions (docs/KERNELS.md): the >=45% target is judged on
        # mfu_model (parameter matmuls + recurrent state math); mfu_hw
        # additionally counts the chunked algorithm's Gram/decay matmuls
        "mfu_model": r["mfu_model"],
        "mfu_hw": r["mfu_hw"],
        "step_ms": r["step_ms"],
        "device": dev.device_kind,
        "batch": [spec["B"], spec["T"]],
        "ssm_impl": r["ssm_impl"],
        "remat": r["remat"],
        "loss": r["loss"],
    }
    # vs_baseline is only defined for the reference's model + seq_len
    if spec["preset"] == BASELINE_PRESET and spec["T"] == BASELINE_T:
        out["vs_baseline"] = round(r["tok_per_sec"] / BASELINE_TOK_PER_SEC, 4)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
