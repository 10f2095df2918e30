"""Chip smoke: the 280M trainer and the serving engine on the TPU, through
the entry points a user calls.

  python chip_smoke.py

Four phases in one process, each released before the next is built:

  A  train.py --preset mamba2-280m   (B=8, accum 2, 3 steps + validation)
  B  ServingEngine(mamba2-280m).run  (6 requests, prompts 8-600, 32 new)
  C  ServingEngine(hybrid-280m).run  (same traffic; ragged paged Pallas
                                      attention, asserted not interpreted)
  D  train.py --preset hybrid-280m   (flash attention forward + backward)

Full width (d_model 768, 64 layers, T=1024, vocab 50,304), random weights
from a seed, synthetic shards from a seed.  One JSON line per phase, one
summary line (per-phase verdicts, multichip, ``"claim": null``), and then,
the last line on stdout, the verdict with exactly these keys:

  {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}

Exit 0 only if every check in every phase held.  Without a TPU, with
``MDT_PALLAS_INTERPRET`` / ``MDT_ATTN_IMPL`` set, or outside the
repository, it exits non-zero with one line saying why and prints no
result.  With four or more chips it also runs data-parallel training and
a data-sharded engine across four of them.

The times printed are bring-up facts (did it start, how long does a cold
compile take), not benchmark numbers: there is no steady window here.
"""

from __future__ import annotations

import gc
import importlib.metadata
import json
import math
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
LOG_ROOT = os.path.join(REPO, "log", "chip_smoke")  # log/ is git-ignored

# prompts 8-600 tokens; two exceed prefill_chunk_tokens (256) so the chunk
# step compiles; 600 + 32 fits the hybrid's kv_slot_tokens (1024)
PROMPT_LENS = (8, 40, 120, 250, 300, 600)
NEW_TOKENS = 32
CAPACITY = 8


def _refuse(why: str) -> None:
    print(f"chip_smoke: {why}", file=sys.stderr)
    raise SystemExit(1)


def _first_span_end_s(events: list[dict], name: str) -> float:
    """Wall-clock end of the first ``name`` span in a SpanTracer stream."""
    wall_t0 = next(e["wall_t0_s"] for e in events
                   if e["kind"] == "trace_header")
    span = next(e for e in events
                if e["kind"] == "span" and e["name"] == name)
    return wall_t0 + (span["t_ms"] + span["dur_ms"]) / 1000.0


class _Phase:
    """Collects one phase's facts and failed checks into its JSON line."""

    def __init__(self, name: str, cache_dir: str):
        self.rec = {"phase": name}
        self.failures: list[str] = []
        self._cache_dir = cache_dir
        self._entries_before = _cache_entries(cache_dir)
        self.t0_wall = time.time()
        self.t0 = time.perf_counter()

    def check(self, ok: bool, what: str) -> None:
        if not ok:
            self.failures.append(what)

    def first_result_at(self, wall_s: float) -> None:
        total = time.perf_counter() - self.t0
        first = wall_s - self.t0_wall
        self.rec["seconds_to_first_result"] = round(first, 1)
        self.rec["seconds_after_first_result"] = round(total - first, 1)

    def finish(self, devices_used: int) -> dict:
        import jax
        import jaxlib

        devices = jax.devices()
        self.rec.update(
            ok=not self.failures,
            failures=self.failures,
            platform=devices[0].platform,
            device_kind=devices[0].device_kind,
            devices_used=devices_used,
            devices_present=len(devices),
            jax=jax.__version__,
            jaxlib=jaxlib.__version__,
            libtpu=importlib.metadata.version("libtpu"),
            seconds_total=round(time.perf_counter() - self.t0, 1),
            compile_cache_dir=self._cache_dir,
            compile_cache_entries_before=self._entries_before,
            compile_cache_entries_after=_cache_entries(self._cache_dir),
            # process-lifetime peak, and what is still held once the
            # phase has been released (per device)
            peak_bytes_in_use=_per_device("peak_bytes_in_use"),
            bytes_in_use_after_release=_per_device("bytes_in_use"),
        )
        print(json.dumps(self.rec), flush=True)
        return self.rec


def _per_device(stat: str) -> list:
    import jax

    return [(d.memory_stats() or {}).get(stat) for d in jax.devices()]


def _cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def _release() -> None:
    """Drop the finished phase's arrays and executables from the chip."""
    import jax

    jax.clear_caches()
    gc.collect()


# ------------------------------------------------------------------ train


def train_phase(name: str, preset: str, cache_dir: str,
                mesh_data: int = 1) -> dict:
    """``train.main()`` for 3 steps at B=8 per data shard, accum 2."""
    import train
    from mamba_distributed_tpu.config import get_preset
    from mamba_distributed_tpu.data import native
    from mamba_distributed_tpu.obs.export import load_jsonl

    ph = _Phase(name, cache_dir)
    log_dir = os.path.join(LOG_ROOT, name)
    argv = ["train.py", "--preset", preset, "--micro-batch-size", "8",
            "--total-batch-size", str(16384 * mesh_data), "--max-steps", "3",
            "--log-dir", log_dir, "--spans"]
    if mesh_data > 1:
        argv += ["--mesh-data", str(mesh_data)]
    ph.rec["command"] = " ".join(argv)
    ph.rec["shard_reader"] = "native" if native.available() else "numpy"
    old_argv = sys.argv
    sys.argv = argv
    try:
        train.main()
    finally:
        sys.argv = old_argv
    _release()

    records = load_jsonl(os.path.join(log_dir, "metrics.jsonl"))
    steps = [r for r in records if r["kind"] == "train"]
    vals = [r for r in records if r["kind"] == "val"]
    losses = [r["loss"] for r in steps]
    norms = [r["grad_norm"] for r in steps]
    val_losses = [r["loss"] for r in vals]
    ph.rec.update(train_losses=losses, val_losses=val_losses,
                  grad_norms=norms)
    # uniform logits at random init: loss = ln(padded vocab) = 10.83
    expect = math.log(get_preset(preset).model.vocab_size_padded)
    ph.check(len(steps) == 3, f"expected 3 train steps, logged {len(steps)}")
    ph.check(bool(vals) and vals[0]["step"] == 0, "no step-0 validation")
    # the jsonl writer turns a non-finite value into null
    ph.check(all(isinstance(x, float) and math.isfinite(x)
                 for x in losses + val_losses), "non-finite loss")
    ph.check(bool(losses) and isinstance(losses[0], float)
             and abs(losses[0] - expect) <= 0.5,
             f"first train loss {losses[:1]} not within 0.5 of {expect:.2f}")
    ph.check(all(isinstance(g, float) and math.isfinite(g) and g > 0
                 for g in norms), "grad norm not finite and > 0")
    if mesh_data > 1:
        # every chip of the mesh held a replica's worth of trainer state
        # (an idle chip reports a few KB)
        peaks = _per_device("peak_bytes_in_use")[:mesh_data]
        ph.check(all(p is not None and p > 2**30 for p in peaks),
                 f"a chip of the mesh held no trainer state: peaks {peaks}")
    events = load_jsonl(os.path.join(log_dir, "events.jsonl"))
    ph.first_result_at(_first_span_end_s(events, "eval"))
    return ph.finish(devices_used=mesh_data)


# ------------------------------------------------------------------ serve


def _requests(vocab_size: int):
    import numpy as np

    from mamba_distributed_tpu.serving import GenerationRequest

    rng = np.random.default_rng(0)
    return [
        GenerationRequest(
            prompt_ids=rng.integers(0, vocab_size, size=n).astype(np.int32),
            max_new_tokens=NEW_TOKENS, seed=i,
        )
        for i, n in enumerate(PROMPT_LENS)
    ]


def _tokens_outside_reference_top_k(params, cfg, request, new_tokens,
                                    tol: float = 0.05) -> int:
    """How many served tokens an independent computation would not have
    offered the sampler.

    The prompt plus the served tokens are teacher-forced through
    ``lm_forward`` — the training path: one full-sequence chunked scan
    and flash attention, no cache, no pages — and each served token's
    reference logit must reach the reference's top-k cut.  ``tol`` is a
    few bf16 steps at the logits' magnitude: at random init the cut runs
    through near-ties, and the two paths round differently.  A token
    drawn from anywhere else in the vocabulary sits far below the cut.
    """
    import jax.numpy as jnp
    import numpy as np

    from mamba_distributed_tpu.inference.generate import vocab_pad_mask
    from mamba_distributed_tpu.models import lm_forward

    ids = np.concatenate([np.asarray(request.prompt_ids), new_tokens])
    n = len(ids)
    padded = np.zeros((1, -(-n // 256) * 256), np.int32)  # causal: the
    padded[0, :n] = ids                      # right pad changes nothing
    logits = lm_forward(params, cfg, jnp.asarray(padded))
    # the logits at position p are the distribution of token p + 1
    rows = np.asarray(
        logits[0, len(request.prompt_ids) - 1:n - 1], np.float32
    ) + np.asarray(vocab_pad_mask(cfg))
    cut = np.sort(rows, axis=1)[:, -request.top_k]
    served = rows[np.arange(len(new_tokens)), new_tokens]
    return int((served < cut - tol).sum())


def serve_phase(name: str, preset: str, cache_dir: str,
                data_shards: int = 1) -> dict:
    """``init_lm_params`` -> ``ServingEngine`` -> ``.run(requests)``."""
    import dataclasses

    import jax
    import numpy as np

    from mamba_distributed_tpu.config import get_preset
    from mamba_distributed_tpu.inference import generate
    from mamba_distributed_tpu.models import init_lm_params
    from mamba_distributed_tpu.obs import SpanTracer
    from mamba_distributed_tpu.obs.export import load_jsonl
    from mamba_distributed_tpu.ops.pallas import attention_kernels
    from mamba_distributed_tpu.ops.pallas.common import (
        resolve_attn_impl,
        resolve_interpret,
    )
    from mamba_distributed_tpu.serving import ServingEngine
    from mamba_distributed_tpu.serving import engine as engine_mod
    from mamba_distributed_tpu.serving import prefill as prefill_mod

    ph = _Phase(name, cache_dir)
    cfg = get_preset(preset).model
    if data_shards > 1:
        cfg = dataclasses.replace(cfg, serving_data_shards=data_shards)
    # before the engine is built: what "auto" means on this backend
    ph.check(resolve_attn_impl("auto") == "pallas",
             f"attn_impl auto resolved to {resolve_attn_impl('auto')!r}")
    ph.check(resolve_interpret(None) is False,
             "Pallas kernels would run interpreted")
    kernel_traces0 = dict(attention_kernels.TRACE_COUNTS)
    engine_traces0 = {**engine_mod.TRACE_COUNTS, **prefill_mod.TRACE_COUNTS}

    params = jax.jit(lambda k: init_lm_params(k, cfg))(jax.random.PRNGKey(0))
    log_dir = os.path.join(LOG_ROOT, name)
    os.makedirs(log_dir, exist_ok=True)
    events_path = os.path.join(log_dir, "events.jsonl")
    engine = ServingEngine(params, cfg, capacity=CAPACITY,
                           tracer=SpanTracer(events_path))
    requests = _requests(cfg.vocab_size)
    results = engine.run(requests)  # raises if any admission failed

    ph.check(len(results) == len(requests),
             f"{len(results)} results for {len(requests)} requests")
    ph.check(engine.pending == 0, f"{engine.pending} request(s) not done")
    for r, n in zip(results, PROMPT_LENS):
        toks = np.asarray(r.new_tokens)
        ph.check(r.finish_reason == "length" and toks.shape == (NEW_TOKENS,),
                 f"prompt {n}: finished {r.finish_reason!r} with "
                 f"{toks.shape[0]} tokens")
        ph.check(bool(((toks >= 0) & (toks < cfg.vocab_size)).all()),
                 f"prompt {n}: token id outside [0, {cfg.vocab_size})")
    traces = {**engine_mod.TRACE_COUNTS, **prefill_mod.TRACE_COUNTS}
    ph.rec["jit_traces"] = {k: traces[k] - engine_traces0[k] for k in traces}
    kernel_traces = {k: attention_kernels.TRACE_COUNTS[k] - kernel_traces0[k]
                     for k in kernel_traces0}
    ph.rec["pallas_kernel_traces"] = kernel_traces
    if cfg.attn_layer_idx:
        ph.check(all(v > 0 for v in kernel_traces.values()),
                 f"ragged paged kernels not traced: {kernel_traces}")
    if data_shards > 1:
        shards = {len(x.addressable_shards)
                  for x in jax.tree.leaves(engine.pool)}
        ph.rec["pool_leaf_shard_counts"] = sorted(shards)
        ph.check(shards == {data_shards},
                 f"pool leaves have {sorted(shards)} addressable shards")
        # while the engine is alive: replicated weights + a pool shard
        held = _per_device("bytes_in_use")[:data_shards]
        ph.rec["bytes_in_use_while_serving"] = held
        ph.check(all(b is not None and b > 2**27 for b in held),
                 f"a chip of the mesh holds no weights: in use {held}")
    ph.first_result_at(
        _first_span_end_s(load_jsonl(events_path), "serving_tick"))

    # reference on a small input, asserted: the shortest (one-shot
    # prefill) and the longest (chunked prefill) request against the
    # full-sequence forward
    for i in (0, len(requests) - 1):
        outside = _tokens_outside_reference_top_k(
            params, cfg, requests[i], np.asarray(results[i].new_tokens))
        ph.rec.setdefault("tokens_outside_reference_top_k", {})[
            f"prompt_{PROMPT_LENS[i]}"] = f"{outside}/{NEW_TOKENS}"
        ph.check(outside == 0,
                 f"prompt {PROMPT_LENS[i]}: {outside} served token(s) the "
                 f"full forward would not have offered the sampler")
    # identity with solo generate() is reported, not asserted: the tests
    # pin it under "highest" matmul precision, the chip runs bf16 passes,
    # and at random init the top-k cut runs through near-ties
    r0 = requests[0]
    solo = generate(params, cfg, np.asarray(r0.prompt_ids)[None],
                    jax.random.PRNGKey(r0.seed), max_new_tokens=NEW_TOKENS)
    solo_new = np.asarray(solo)[0, len(r0.prompt_ids):]
    ph.rec["tokens_equal_to_solo_generate"] = (
        f"{int((solo_new == np.asarray(results[0].new_tokens)).sum())}"
        f"/{NEW_TOKENS}")
    del engine, params, results, solo
    _release()
    return ph.finish(devices_used=data_shards)


# ------------------------------------------------------------------- main


def _run(phases, fn, *args, **kw) -> None:
    """One phase; a crash is a failed phase (recorded with its traceback,
    exit code non-zero), and the later phases still run."""
    name = args[0]
    try:
        phases[name] = fn(*args, **kw)
    except Exception:  # noqa: BLE001 — boundary: report, keep going, exit 1
        tb = traceback.format_exc()
        print(tb, file=sys.stderr, flush=True)
        phases[name] = {"phase": name, "ok": False,
                        "failures": [tb.strip().splitlines()[-1]]}
        print(json.dumps(phases[name]), flush=True)
        _release()


def run_multichip(phases: dict, cache_dir: str) -> None:
    """Data-parallel training and a data-sharded engine over four chips."""
    _run(phases, train_phase, "multichip_train", "mamba2-280m", cache_dir,
         mesh_data=4)
    _run(phases, serve_phase, "multichip_serve", "mamba2-280m", cache_dir,
         data_shards=4)


def verdict_line(ok: bool, devices) -> str:
    """The last line on stdout: these keys and no others (the driver
    parses it); everything else goes on the summary line before it."""
    return json.dumps({
        "ok": ok,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)},
    })


def main() -> int:
    for var in ("MDT_PALLAS_INTERPRET", "MDT_ATTN_IMPL"):
        if var in os.environ:
            _refuse(f"{var} is set; it steers the kernels this smoke "
                    f"exists to compile — unset it")
    if not os.path.isdir(os.path.join(REPO, "mamba_distributed_tpu")):
        _refuse(f"no mamba_distributed_tpu package beside {__file__}; run "
                f"it from a checkout of the repository")
    sys.path.insert(0, REPO)
    os.chdir(REPO)  # the presets' data and log directories are relative

    import jax

    from mamba_distributed_tpu.utils import platform
    try:
        platform.init_backend()
    except SystemExit as e:  # the one owner's reason under this tool's name
        _refuse(e.code)
    cache_dir = platform.configure_compile_cache()
    n_dev = len(jax.devices())

    phases: dict[str, dict] = {}
    _run(phases, train_phase, "A_train_mamba2_280m", "mamba2-280m", cache_dir)
    _run(phases, serve_phase, "B_serve_mamba2_280m", "mamba2-280m", cache_dir)
    _run(phases, serve_phase, "C_serve_hybrid_280m", "hybrid-280m", cache_dir)
    _run(phases, train_phase, "D_train_hybrid_280m", "hybrid-280m", cache_dir)
    if n_dev >= 4:
        run_multichip(phases, cache_dir)
        multichip = {k: v["ok"] for k, v in phases.items()
                     if k.startswith("multichip")}
    else:
        multichip = f"not run: {n_dev} device(s)"

    ok = all(p["ok"] for p in phases.values())
    print(json.dumps({
        "phases": {k: v["ok"] for k, v in phases.items()},
        "multichip": multichip,
        "claim": None,
    }), flush=True)
    print(verdict_line(ok, jax.devices()), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
