"""Decode-throughput benchmark: recurrent O(1)-per-token generation.

The reference's generate loop re-runs the entire growing prefix through
the model for every new token (/root/reference/model.py:49-75,
train.py:176-194) — O(T) work per token.  This framework decodes from
carried conv/SSM state (inference/generate.py), so per-token cost is
O(1); this script measures that as sampled tokens/sec/chip.

Prints one JSON line; ``--json PATH`` also writes it to PATH (the
machine-readable bench artifact BENCH_SERVING.json collects).  Env
knobs: DECODE_B (default 8), DECODE_PROMPT (default 128), DECODE_NEW
(default 256), BENCH_PRESET.  ``--model-shards N``
decodes with the weights tensor-parallel over a 2-D serving mesh's
model axis (``generate(mesh=)``; docs/SERVING.md "2-D serving mesh").

``--hybrid-paged`` benches the RAGGED PAGED attention decode instead
(BENCH_PRESET defaults to hybrid-tiny there): a serving-style slot pool
at LOW occupancy — DECODE_LIVE (2) of DECODE_SLOTS (8) slots live at
DECODE_KV_LEN (96) cached tokens — decoded two ways through the same
``lm_step``; ``--occupancy 0.25,0.5,1.0`` sweeps the live-slot fraction
instead and appends a paged-vs-dense row per fill level
(``occupancy_sweep`` in the JSON record, collected by
BENCH_SERVING.json):

  * paged: the page-table slice covers only the pow2 bucket of pages
    the live slots actually occupy (what serving/engine.py's tick
    does), so attention reads scale with resident tokens;
  * dense fallback: the table spans every slot's FULL kv_slot_tokens
    budget — the cost a batch-max-length dense cache (one shared length
    scalar) would pay every tick.

The ratio is the paged win at that occupancy; on TPU the Pallas ragged
kernel (ops/pallas/attention_kernels.py) additionally skips dead slots'
work entirely.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mamba_distributed_tpu.utils.metrics import emit_bench_record  # noqa: E402

_T0 = time.time()


def _progress(msg: str) -> None:
    print(f"[decode +{time.time() - _T0:6.1f}s] {msg}", file=sys.stderr, flush=True)


def _hybrid_paged_bench(args) -> dict:
    """Paged decode vs the dense batch-max-length cost, optionally swept
    over pool occupancy (``--occupancy 0.25,0.5,1.0``)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mamba_distributed_tpu.config import get_preset
    from mamba_distributed_tpu.models import init_lm_params
    from mamba_distributed_tpu.models.lm import init_lm_blocks_state, lm_step
    from mamba_distributed_tpu.serving import state_cache
    from mamba_distributed_tpu.serving.prefill import cast_decode_params

    preset = os.environ.get("BENCH_PRESET", "hybrid-tiny")
    cfg = get_preset(preset).model
    if not cfg.attn_layer_idx:
        raise SystemExit(f"--hybrid-paged needs a hybrid preset, got {preset}")
    from mamba_distributed_tpu.ops.quant import apply_dtype_overrides

    cfg = apply_dtype_overrides(cfg, weight_dtype=args.weight_dtype,
                                kv_dtype=args.kv_dtype)
    if os.environ.get("DECODE_KV_SLOT"):
        # per-slot KV budget = the dense fallback's read span; raising it
        # models a longer-context pool (dense pays more, paged doesn't)
        import dataclasses

        cfg = dataclasses.replace(
            cfg, kv_slot_tokens=int(os.environ["DECODE_KV_SLOT"])
        )
    S = int(os.environ.get("DECODE_SLOTS", "8"))
    kv_len0 = int(os.environ.get("DECODE_KV_LEN", "96"))
    steps = int(os.environ.get("DECODE_NEW", "64"))
    iters = int(os.environ.get("BENCH_ITERS", "3"))
    pg = cfg.kv_page_tokens
    W_full = cfg.kv_pages_per_slot
    dev = jax.devices()[0]
    if args.occupancy:
        live_counts = sorted({
            max(1, min(S, round(float(f) * S)))
            for f in args.occupancy.split(",")
        })
    else:
        live_counts = [int(os.environ.get("DECODE_LIVE", "2"))]

    params = cast_decode_params(
        jax.jit(lambda k: init_lm_params(k, cfg))(jax.random.PRNGKey(0)),
        cfg=cfg,
    )
    jax.block_until_ready(params)
    _progress(f"params ready ({preset}); S={S} live={live_counts} "
              f"kv_len={kv_len0}")

    A = len(cfg.attn_layer_idx)
    nkv, hd = cfg.effective_attn_num_kv_heads, cfg.effective_attn_head_dim
    n_pages = state_cache.hybrid_pool_pages(cfg, S)
    key = jax.random.PRNGKey(1)
    if cfg.kv_quantized:
        # int8 pools: random int8 pages + per-(page, head) scales — the
        # serving layout the kernels dequantize in-register
        kq = jax.random.randint(key, (A, n_pages + 1, nkv, pg, hd),
                                -127, 128, jnp.int8)
        ks = 0.01 * jnp.ones((A, n_pages + 1, nkv), jnp.float32)
        attn_blocks = (kq, kq, ks, ks)
    else:
        kv = jax.random.normal(key, (A, n_pages + 1, nkv, pg, hd),
                               jnp.dtype(cfg.compute_dtype))
        attn_blocks = (kv, kv)
    state_blocks = {
        "blocks": init_lm_blocks_state(cfg, S),
        "attn_blocks": attn_blocks,
    }
    need = -(-(kv_len0 + steps) // pg)

    @functools.partial(jax.jit, static_argnames=("cfg", "steps"))
    def decode_run(params, state, tbl, lengths, live, tok, cfg, steps):
        def one(carry, _):
            state, lengths, tok = carry
            st = {**state, "attn_meta": (tbl, lengths)}
            logits, st = lm_step(params, cfg, st, tok, write_mask=live)
            lengths = st["attn_meta"][1]
            st = {k: v for k, v in st.items() if k != "attn_meta"}
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (st, lengths, tok), None

        (state, lengths, tok), _ = jax.lax.scan(
            one, (state, lengths, tok), None, length=steps
        )
        return state, tok

    from mamba_distributed_tpu.inference.bucketing import next_pow2_bucket

    # same bucket rule the engine's tick uses, so the bench measures
    # exactly what serving pays
    bucket = min(next_pow2_bucket(need, min_bucket=1), W_full)

    def bench_point(live_n: int) -> dict:
        # serving-style pool state: live slots hold kv_len0 cached tokens
        # in allocator-issued pages, dead slots point at trash
        alloc = state_cache.PagePool(n_pages)
        tbl = np.zeros((S, W_full), np.int32)
        lengths = np.zeros((S,), np.int32)
        for s in range(live_n):
            ids = alloc.alloc(need)
            tbl[s, :need] = ids
            lengths[s] = kv_len0
        live = np.zeros((S,), bool)
        live[:live_n] = True

        def run_width(n_pages_width: int) -> float:
            t = jnp.asarray(tbl[:, :n_pages_width])
            ln = jnp.asarray(lengths)
            lv = jnp.asarray(live)
            tok = jnp.zeros((S,), jnp.int32)
            out = decode_run(params, state_blocks, t, ln, lv, tok,
                             cfg=cfg, steps=steps)
            jax.block_until_ready(out)  # warm/compile
            t0 = time.perf_counter()
            for _ in range(iters):
                out = decode_run(params, state_blocks, t, ln, lv, tok,
                                 cfg=cfg, steps=steps)
            jax.block_until_ready(out)
            return (time.perf_counter() - t0) / iters

        dt_paged = run_width(bucket)
        dt_dense = run_width(W_full)
        _progress(f"live {live_n}/{S}: paged {dt_paged * 1000:.1f} ms, "
                  f"dense {dt_dense * 1000:.1f} ms "
                  f"({dt_dense / dt_paged:.2f}x)")
        return {
            "occupancy": round(live_n / S, 4),
            "live_slots": live_n,
            "tokens_per_sec_paged": round(live_n * steps / dt_paged, 1),
            "tokens_per_sec_dense": round(live_n * steps / dt_dense, 1),
            "paged_vs_dense_speedup": round(dt_dense / dt_paged, 2),
            "kv_pages_in_use": alloc.pages_in_use,
        }

    points = [bench_point(n) for n in live_counts]
    head = points[0]
    record = {
        "metric": f"hybrid_paged_decode_tokens_per_sec_{preset.replace('-', '_')}",
        "value": head["tokens_per_sec_paged"],
        "unit": "sampled tokens/sec (live slots, paged page-bucket)",
        "dense_fallback_tokens_per_sec": head["tokens_per_sec_dense"],
        "paged_vs_dense_speedup": head["paged_vs_dense_speedup"],
        "slots": S,
        "live_slots": head["live_slots"],
        "kv_len": kv_len0,
        "decode_steps": steps,
        "kv_page_tokens": pg,
        "bucket_pages": bucket,
        "dense_pages": W_full,
        "kv_pages_in_use": head["kv_pages_in_use"],
        "kv_pool_pages": n_pages,
        "device": dev.device_kind,
    }
    if cfg.kv_quantized or cfg.serving_weight_dtype == "int8":
        record["quantized"] = {"weights": cfg.serving_weight_dtype,
                               "kv": cfg.kv_page_dtype}
    if args.occupancy:
        record["occupancy_sweep"] = points
    return record


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the JSON record to PATH")
    ap.add_argument("--hybrid-paged", action="store_true",
                    help="bench ragged paged hybrid decode at low "
                         "occupancy vs the dense batch-max-length cost")
    ap.add_argument("--occupancy", default=None, metavar="F1,F2,...",
                    help="with --hybrid-paged: sweep pool occupancy "
                         "fractions (e.g. 0.25,0.5,1.0 => live slots = "
                         "fraction * DECODE_SLOTS) and record a "
                         "paged-vs-dense row per fill level")
    ap.add_argument("--model-shards", type=int, default=0, metavar="N",
                    help="decode with the weights tensor-parallel N-way "
                         "over a 2-D serving mesh's model axis "
                         "(generate(mesh=); on CPU combine with "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=K)")
    ap.add_argument("--weight-dtype", default=None,
                    choices=["bf16", "int8"],
                    help="decode weight dtype (cfg.serving_weight_dtype; "
                         "int8 = per-channel quantized weights)")
    ap.add_argument("--kv-dtype", default=None, choices=["bf16", "int8"],
                    help="KV page dtype for --hybrid-paged "
                         "(cfg.kv_page_dtype; int8 = quantized pages + "
                         "per-page scales)")
    ap.add_argument("--spec-tokens", type=int, default=0, metavar="K",
                    help="speculative greedy decode (cfg.spec_tokens=K; "
                         "batch-1 n-gram drafting over a repetitive "
                         "prompt): times the spec generate() path vs "
                         "the non-speculative greedy baseline — "
                         "token-identical streams, fewer full-model "
                         "launches (docs/SERVING.md 'Speculative "
                         "decoding')")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from mamba_distributed_tpu.utils.platform import configure_compile_cache

    configure_compile_cache()
    _progress("initializing backend...")
    dev = jax.devices()[0]
    _progress(f"backend up: {dev.device_kind}")

    if args.hybrid_paged:
        emit_bench_record(_hybrid_paged_bench(args), args.json)
        return

    from mamba_distributed_tpu.config import get_preset
    from mamba_distributed_tpu.inference import generate
    from mamba_distributed_tpu.models import init_lm_params

    B = int(os.environ.get("DECODE_B", "8"))
    prompt_len = int(os.environ.get("DECODE_PROMPT", "128"))
    new_tokens = int(os.environ.get("DECODE_NEW", "256"))
    preset = os.environ.get("BENCH_PRESET", "mamba2-280m")
    cfg = get_preset(preset).model
    from mamba_distributed_tpu.ops.quant import apply_dtype_overrides

    cfg = apply_dtype_overrides(cfg, weight_dtype=args.weight_dtype,
                                kv_dtype=args.kv_dtype)

    key = jax.random.PRNGKey(0)
    params = jax.jit(lambda k: init_lm_params(k, cfg))(key)
    jax.block_until_ready(params)
    _progress("params initialized")

    mesh = None
    if args.model_shards > 1:
        from mamba_distributed_tpu.parallel.mesh import serving_mesh
        from mamba_distributed_tpu.parallel.sharding import (
            serving_param_shardings,
            validate_serving_model_shards,
        )

        validate_serving_model_shards(cfg, args.model_shards)
        mesh = serving_mesh(1, model_shards=args.model_shards)
        # commit the tp layout up front so the timed loop never pays a
        # host->sharded transfer (the engine device_puts the same way)
        params = jax.device_put(params, serving_param_shardings(params, mesh))
        jax.block_until_ready(params)
        _progress(f"weights tensor-parallel over {args.model_shards} shards")

    if args.spec_tokens:
        # batch-1 greedy speculative decode on a repetitive prompt (the
        # workload n-gram drafting predicts): spec vs non-spec greedy,
        # streams asserted token-identical (speculation is lossless)
        import dataclasses

        import numpy as np

        pattern = np.random.default_rng(0).integers(
            0, cfg.vocab_size, size=8).astype(np.int32)
        prompt = jnp.asarray(
            np.tile(pattern, -(-prompt_len // 8))[:prompt_len]
        )[None, :]
        # fp32 compute keeps spec == baseline exactly token-identical
        # (bf16 chunk-vs-step rounding can flip a rare near-tie argmax;
        # docs/SERVING.md "Speculative decoding") — CPU XLA widens bf16
        # anyway, so the timing comparison is unaffected
        cfg = dataclasses.replace(cfg, compute_dtype="float32")
        spec_cfg = dataclasses.replace(cfg, spec_tokens=args.spec_tokens)
        iters = int(os.environ.get("BENCH_ITERS", "3"))
        out = {}
        streams = {}
        for name, c in (("spec", spec_cfg), ("baseline", cfg)):
            run = lambda c=c: generate(params, c, prompt,
                                       jax.random.PRNGKey(2),
                                       max_new_tokens=new_tokens,
                                       top_k=1)
            res = run()
            jax.block_until_ready(res)  # warm every signature
            t0 = time.time()
            for _ in range(iters):
                res = run()
            jax.block_until_ready(res)
            dt = (time.time() - t0) / iters
            streams[name] = jnp.asarray(res)[0, prompt_len:].tolist()
            out[f"tokens_per_sec_{name}"] = round(new_tokens / dt, 1)
            _progress(f"{name}: {out[f'tokens_per_sec_{name}']} tok/s")
        assert streams["spec"] == streams["baseline"], \
            "speculative stream diverged from greedy baseline"
        record = {
            "metric": (f"decode_spec_tokens_per_sec_"
                       f"{preset.replace('-', '_')}"),
            "value": out["tokens_per_sec_spec"],
            "unit": ("sampled tokens/sec (batch-1 greedy, "
                     f"K={args.spec_tokens} ngram drafts)"),
            **out,
            "spec_vs_baseline_speedup": round(
                out["tokens_per_sec_spec"]
                / out["tokens_per_sec_baseline"], 2),
            "spec_tokens": args.spec_tokens,
            "prompt_len": prompt_len,
            "new_tokens": new_tokens,
            "device": dev.device_kind,
        }
        emit_bench_record(record, args.json)
        return

    kp, kg = jax.random.split(jax.random.PRNGKey(1))
    prompt = jax.random.randint(kp, (B, prompt_len), 0, cfg.vocab_size, jnp.int32)

    out = generate(params, cfg, prompt, kg, max_new_tokens=new_tokens,
                   mesh=mesh)
    jax.block_until_ready(out)
    _progress("generate compiled + warm run done")

    iters = int(os.environ.get("BENCH_ITERS", "3"))
    t0 = time.time()
    for i in range(iters):
        out = generate(
            params, cfg, prompt, jax.random.fold_in(kg, i),
            max_new_tokens=new_tokens, mesh=mesh,
        )
    jax.block_until_ready(out)
    dt = (time.time() - t0) / iters

    tok_per_sec = B * new_tokens / dt
    record = {
        "metric": f"decode_tokens_per_sec_per_chip_{preset.replace('-', '_')}",
        "value": round(tok_per_sec, 1),
        "unit": "sampled tokens/sec/chip",
        "per_token_ms": round(1000 * dt / new_tokens, 3),
        "batch": B,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "device": dev.device_kind,
    }
    if mesh is not None:
        record["model_shards"] = args.model_shards
    if cfg.serving_weight_dtype == "int8":
        record["quantized"] = {"weights": cfg.serving_weight_dtype,
                               "kv": cfg.kv_page_dtype}
    emit_bench_record(record, args.json)


if __name__ == "__main__":
    main()
