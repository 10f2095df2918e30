"""Train a Mamba LM on TPU.

TPU-native replacement for the reference's ``torchrun --standalone
--nproc_per_node=8 train.py`` (/root/reference/README.md:16): no process-
per-device — one process per host, a `jax.sharding.Mesh` over the chips,
and XLA SPMD for every collective.

Examples:
  python train.py --preset mamba2-280m --max-steps 30
  python train.py --preset mamba2-280m-dp8            # 8-chip data parallel
  python train.py --preset mamba2-1.3b-fsdp16         # FSDP
  python train.py --preset mamba2-280m --mesh-data 4  # override mesh axes
"""

from __future__ import annotations

import argparse
import dataclasses

import jax


def parse_args():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--preset", default="mamba2-280m",
                   help="one of config.PRESETS")
    p.add_argument("--max-steps", type=int, default=None)
    p.add_argument("--data-dir", default=None)
    p.add_argument("--log-dir", default=None)
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-every", type=int, default=None,
                   help="steps between checkpoints (preset default 1000, "
                        "the reference's cadence)")
    p.add_argument("--resume", action="store_true",
                   help="resume from the latest checkpoint in --checkpoint-dir")
    p.add_argument("--micro-batch-size", type=int, default=None)
    p.add_argument("--total-batch-size", type=int, default=None)
    p.add_argument("--seq-len", type=int, default=None)
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--mesh-data", type=int, default=None)
    p.add_argument("--mesh-fsdp", type=int, default=None)
    p.add_argument("--mesh-seq", type=int, default=None)
    p.add_argument("--mesh-tensor", type=int, default=None)
    p.add_argument("--ssm-impl", choices=["xla", "pallas"], default=None,
                   help="kernel backend for the SSM scan")
    p.add_argument("--attn-impl", choices=["auto", "xla", "pallas"],
                   default=None,
                   help="SDPA backend for hybrid attention layers (pallas: "
                        "flash kernel)")
    p.add_argument("--attn-sp-impl", choices=["ring", "ulysses"], default=None,
                   help="attention strategy under sequence parallelism "
                        "(ring: KV rotation; ulysses: all-to-all head "
                        "sharding, needs heads %% mesh-seq == 0)")
    p.add_argument("--remat-policy", choices=["all", "dots", "mixer"],
                   default=None)
    p.add_argument("--chunk-size", type=int, default=None,
                   help="SSD chunk length (numerics-neutral perf knob; "
                        "larger chunks measured faster on v5e)")
    p.add_argument("--loss-impl", choices=["dense", "blocked"], default=None,
                   help="LM-head+CE formulation; blocked never "
                        "materializes the (b, t, V) logits")
    p.add_argument("--conv-impl", choices=["shift", "xla_conv"], default=None,
                   help="causal-conv formulation (same math)")
    p.add_argument("--multihost", action="store_true",
                   help="call jax.distributed.initialize() first (TPU pods)")
    p.add_argument("--sample-prompt", default=None, metavar="TEXT",
                   help="sample 4x32-token continuations of TEXT every "
                        "sample_every steps, like the reference's in-loop "
                        "sampling (tokenized by the vendored GPT-2 BPE from "
                        "$GPT2_BPE_DIR / ./gpt2_bpe, tiktoken fallback)")
    p.add_argument("--sample-prompt-ids", default=None, metavar="IDS",
                   help="same, but the prompt as comma-separated token ids "
                        "(no tokenizer needed)")
    p.add_argument("--spans", action="store_true",
                   help="host-side span tracing (obs/): data_load/"
                        "train_step/eval/checkpoint phase timings to "
                        "{log_dir}/events.jsonl, readable by "
                        "scripts/obs_report.py; zero device overhead")
    p.add_argument("--overflow-threshold", type=float, default=None,
                   metavar="NORM",
                   help="on-device divergence sentinel: the train step "
                        "also reports pre-clip global grad norm > NORM "
                        "(counted into the flight record); 0 disables")
    p.add_argument("--no-halt-on-divergence", action="store_true",
                   help="keep training through a non-finite loss instead "
                        "of dumping the flight record and stopping")
    p.add_argument("--auto-restart", type=int, default=0, metavar="N",
                   help="on a crash, rebuild the trainer from the latest "
                        "checkpoint in --checkpoint-dir and continue, up to "
                        "N times (restart-based failure recovery)")
    return p.parse_args()


def resolve_sampling(args):
    """-> (prompt_ids | None, decode_fn | None).

    The reference hardcodes tiktoken-GPT2("Hello, I'm a language model,")
    (/root/reference/train.py:170-171); here the prompt is a flag, and a
    zero-egress environment can pass raw ids instead.
    """
    if args.sample_prompt_ids is not None:
        return [int(t) for t in args.sample_prompt_ids.split(",")], None
    if args.sample_prompt is None:
        return None, None
    from mamba_distributed_tpu.data.gpt2_bpe import load_encoder

    try:
        # vendored zero-egress BPE (local gpt2_bpe/ files), tiktoken fallback
        encode, decode = load_encoder()
    except FileNotFoundError as e:
        raise SystemExit(
            f"--sample-prompt: {e}\nOr pass --sample-prompt-ids instead."
        )
    return encode(args.sample_prompt), decode


def build_config(args):
    from mamba_distributed_tpu.config import get_preset

    cfg = get_preset(args.preset)
    overrides = {}
    for field, arg in [
        ("micro_batch_size", args.micro_batch_size),
        ("total_batch_size", args.total_batch_size),
        ("seq_len", args.seq_len),
        ("seed", args.seed),
        ("checkpoint_every", args.checkpoint_every),
    ]:
        if arg is not None:
            overrides[field] = arg
    mesh_over = {
        k: v for k, v in [
            ("data", args.mesh_data), ("fsdp", args.mesh_fsdp),
            ("seq", args.mesh_seq), ("tensor", args.mesh_tensor),
        ] if v is not None
    }
    if mesh_over:
        overrides["mesh"] = dataclasses.replace(cfg.mesh, **mesh_over)
    model_over = {
        k: v for k, v in [
            ("ssm_impl", args.ssm_impl), ("remat_policy", args.remat_policy),
            ("attn_sp_impl", args.attn_sp_impl),
            ("attn_impl", args.attn_impl),
            ("chunk_size", args.chunk_size),
            ("loss_impl", args.loss_impl),
            ("conv_impl", args.conv_impl),
        ] if v is not None
    }
    if model_over:
        overrides["model"] = dataclasses.replace(cfg.model, **model_over)
    if args.data_dir is not None:
        overrides["data"] = dataclasses.replace(cfg.data, data_dir=args.data_dir)
    tele_over = {}
    if args.spans:
        tele_over["spans"] = True
    if args.overflow_threshold is not None:
        tele_over["overflow_threshold"] = args.overflow_threshold
    if args.no_halt_on_divergence:
        tele_over["halt_on_divergence"] = False
    if tele_over:
        overrides["telemetry"] = dataclasses.replace(cfg.telemetry, **tele_over)
    if args.log_dir is not None:
        overrides["log_dir"] = args.log_dir
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg


def main():
    args = parse_args()
    from mamba_distributed_tpu.utils.platform import configure_compile_cache

    configure_compile_cache()
    if args.multihost:
        jax.distributed.initialize()
    cfg = build_config(args)

    from mamba_distributed_tpu.training import Trainer

    prompt_ids, decode_fn = resolve_sampling(args)
    if args.auto_restart < 0:
        raise SystemExit(f"--auto-restart must be >= 0, got {args.auto_restart}")
    if args.auto_restart and not args.checkpoint_dir:
        raise SystemExit("--auto-restart needs --checkpoint-dir to recover from")

    def make_trainer(resume: bool, after_crash: bool = False):
        trainer = Trainer(cfg, sample_prompt_ids=prompt_ids, decode_fn=decode_fn)
        if resume and args.checkpoint_dir:
            try:
                trainer.restore_checkpoint(args.checkpoint_dir)
                print(f"resumed from step {trainer.step}")
            except FileNotFoundError:
                if after_crash:
                    # a crash before the first checkpoint: a "restart" would
                    # replay from step 0 — no recovery value, just repeated
                    # data and burned restart budget (ADVICE r3)
                    raise SystemExit(
                        "auto-restart: crashed before any checkpoint was "
                        "written; refusing to silently restart from step 0 "
                        "(lower --checkpoint-every or rerun manually)"
                    )
                print("no checkpoint found; starting fresh")
        return trainer

    # restart-based failure recovery (the reference has none: any crash
    # kills the torchrun job, /root/reference/train.py): rebuild from the
    # latest full-state checkpoint and continue, up to --auto-restart times
    trainer = None
    try:
        for attempt in range(args.auto_restart + 1):
            try:
                # (re)build INSIDE the protected block, with the previous
                # trainer's buffers already released: a failed restore or a
                # rebuild OOM consumes restart budget instead of dying, and
                # device memory never holds two full parameter sets
                if trainer is None:
                    trainer = make_trainer(
                        resume=args.resume if attempt == 0 else True,
                        after_crash=attempt > 0,
                    )
                trainer.run(max_steps=args.max_steps,
                            checkpoint_dir=args.checkpoint_dir)
                break
            except Exception as e:
                from mamba_distributed_tpu.obs import DivergenceError

                # a divergence is deterministic from the restored state:
                # a restart would replay the same data/RNG back to the
                # same NaN, burning the whole budget for nothing — the
                # flight record is the actionable artifact, stop here
                if isinstance(e, DivergenceError):
                    raise
                if attempt == args.auto_restart:
                    raise
                print(f"run crashed ({type(e).__name__}: {e}); "
                      f"restart {attempt + 1}/{args.auto_restart} "
                      "from the latest checkpoint")
                if trainer is not None:
                    try:
                        trainer.finish()
                    except Exception:
                        pass
                trainer = None
    finally:
        if trainer is not None:
            trainer.finish()


if __name__ == "__main__":
    main()
