"""Training driver: the reference's train.py loop, TPU-native.

Covers /root/reference/train.py:128-244 — grad-accum training loop,
validation every ``val_every`` steps, reference-format text logging,
periodic checkpointing — with the DDP/NCCL runtime replaced by a
`jax.sharding.Mesh` + jitted step (XLA collectives over ICI/DCN), and
exact resume (params + optimizer + loader position + RNG) that the
reference lacks (train.py:161-162).

Multi-host: each TPU-VM host is one loader "process" (rank-strided shards,
reference dataloader.py:38), and `jax.make_array_from_process_local_data`
assembles the global batch; single-host this degenerates to a device_put.
"""

from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from mamba_distributed_tpu.config import TrainConfig
from mamba_distributed_tpu.data import ShardedTokenLoader, ensure_synthetic_shards
from mamba_distributed_tpu.models import count_params, init_lm_params
from mamba_distributed_tpu.obs import (
    NULL_TRACER,
    DivergenceError,
    DivergenceSentinel,
    SpanTracer,
    annotated,
)
from mamba_distributed_tpu.parallel.mesh import build_mesh
from mamba_distributed_tpu.parallel.sharding import (
    batch_sharding,
    param_shardings,
)
from mamba_distributed_tpu.training.optimizer import lr_schedule, make_optimizer
from mamba_distributed_tpu.training.train_step import make_eval_step, make_train_step
from mamba_distributed_tpu.utils.flops import flops_per_token, peak_flops_per_chip
from mamba_distributed_tpu.utils.metrics import MetricsLogger
from mamba_distributed_tpu.utils.platform import (
    describe_devices,
    key_compile_cache_by_scopes,
)


class Trainer:
    def __init__(
        self,
        cfg: TrainConfig,
        devices=None,
        verbose: bool = True,
        sample_prompt_ids=None,
        decode_fn=None,
    ):
        self.cfg = cfg
        key_compile_cache_by_scopes()  # before the first program compiles
        self.mesh = build_mesh(cfg.mesh, devices)
        self.master = jax.process_index() == 0
        self.verbose = verbose and self.master
        if self.verbose:
            print(f"trainer: {describe_devices(self.mesh)}")

        if cfg.mesh.seq > 1:
            from mamba_distributed_tpu.parallel.seq_parallel import SeqContext

            batch_axes = (
                ("data", "fsdp", "expert") if cfg.mesh.expert > 1
                else ("data", "fsdp")
            )
            self.seq_ctx = SeqContext(self.mesh, "seq", batch_axes)
        else:
            self.seq_ctx = None

        # --- data (synthetic fallback per DataConfig.allow_synthetic;
        # ensure_synthetic_shards is idempotent when shards exist) ---
        data_dir = cfg.data.data_dir
        if cfg.data.allow_synthetic:
            ensure_synthetic_shards(
                data_dir,
                vocab_size=cfg.model.vocab_size,
                tokens_per_shard=cfg.data.synthetic_tokens_per_shard,
                num_shards=cfg.data.synthetic_num_shards,
                seed=cfg.seed,
            )
        dp = cfg.data_parallel_size
        nproc = jax.process_count()
        assert (cfg.micro_batch_size * dp) % nproc == 0
        self.rows_per_host = cfg.micro_batch_size * dp // nproc
        loader_args = dict(
            B=self.rows_per_host,
            T=cfg.seq_len,
            data_dir=data_dir,
            process_rank=jax.process_index(),
            num_processes=nproc,
            master_process=self.verbose,
        )
        self.train_loader = ShardedTokenLoader(split="train", **loader_args)
        self.val_loader = ShardedTokenLoader(split="val", **loader_args)

        # --- model: init directly into the sharded layout ---
        self.rng = jax.random.PRNGKey(cfg.seed)
        self.rng, init_key = jax.random.split(self.rng)
        shapes = jax.eval_shape(lambda k: init_lm_params(k, cfg.model), init_key)
        pshard = param_shardings(shapes, self.mesh, cfg.shard_params)
        self.params = jax.jit(
            lambda k: init_lm_params(k, cfg.model), out_shardings=pshard
        )(init_key)
        if self.verbose:
            n = count_params(self.params)
            print(f"model params: {n:,} (analytic {cfg.model.num_params():,})")

        # --- optimizer (moments inherit param shardings, scalars replicate) ---
        from mamba_distributed_tpu.parallel.sharding import opt_state_shardings

        self.optimizer = make_optimizer(cfg)
        opt_shapes = jax.eval_shape(self.optimizer.init, self.params)
        oshard = opt_state_shardings(opt_shapes, shapes, pshard, self.mesh)
        self.opt_state = jax.jit(self.optimizer.init, out_shardings=oshard)(
            self.params
        )
        self.schedule = lr_schedule(cfg)

        # --- telemetry (obs/): spans + divergence sentinel, host-side only.
        # The tracer/sentinel never see a jax.Array that is not already
        # fetched, so enabling them cannot add device syncs or jit traces
        # (pinned by tests/test_obs.py).
        tcfg = cfg.telemetry
        self.tracer = (
            SpanTracer(os.path.join(cfg.log_dir, "events.jsonl"))
            if tcfg.spans and self.master else NULL_TRACER
        )
        self.sentinel = (
            DivergenceSentinel(
                # every process watches (all must halt together on a
                # divergence); only the master writes the shared dump
                os.path.join(cfg.log_dir, "flight_record.json")
                if self.master else None,
                capacity=tcfg.flight_recorder_len, tracer=self.tracer,
            )
            if tcfg.sentinel else None
        )
        self._overflow_on = tcfg.overflow_threshold > 0

        self.train_step = make_train_step(
            cfg, self.optimizer, self.mesh, self.params, self.opt_state,
            seq_ctx=self.seq_ctx,
            overflow_threshold=(
                tcfg.overflow_threshold if self._overflow_on else None
            ),
        )
        self.eval_step = make_eval_step(
            cfg, self.mesh, self.params, seq_ctx=self.seq_ctx
        )
        self.bshard = batch_sharding(self.mesh, seq_sharded=self.seq_ctx is not None)

        self.logger = MetricsLogger(cfg.log_dir, self.verbose)
        self.step = 0
        self._ckpt = None  # async Checkpointer, created on first save
        self._ckpt_dir = None
        # in-training sampling (reference train.py:166-199): every
        # sample_every steps generate 4 continuations of the prompt.
        # Token ids are injected (no tokenizer download in zero-egress
        # environments); decode_fn, if given, renders them as text.
        self._sample_prompt_ids = sample_prompt_ids
        self._decode_fn = decode_fn
        self._flops_per_token = flops_per_token(cfg.model, cfg.seq_len)
        self._flops_per_token_model = flops_per_token(
            cfg.model, cfg.seq_len, convention="model"
        )
        # MFU is a statement about a TPU: off one, none is logged
        dev = self.mesh.devices.flat[0]
        self._peak = (
            peak_flops_per_chip(dev) * self.mesh.devices.size
            if dev.platform == "tpu" else None
        )

    # ------------------------------------------------------------------

    @property
    def tracer(self):
        return self._tracer

    @tracer.setter
    def tracer(self, tracer) -> None:
        # whoever hands the trainer a tracer (the constructor, a benchmark)
        # gets its spans on the profiler's clock too, the step's span as
        # the profiler's step (obs/tracer.AnnotatedTracer)
        self._tracer = annotated(tracer, step_span="train_step")

    def _global_batch(self, accum: int, loader) -> tuple[jax.Array, jax.Array]:
        xs, ys = [], []
        for _ in range(accum):
            x, y = loader.next_batch()
            xs.append(x)
            ys.append(y)
        x = np.stack(xs)  # (accum, B_local, T)
        y = np.stack(ys)
        # leading accum axis replicated; batch (and maybe seq) axes sharded
        from jax.sharding import NamedSharding, PartitionSpec as P

        ashard = NamedSharding(self.mesh, P(None, *self.bshard.spec))
        make = lambda arr: jax.make_array_from_process_local_data(ashard, arr)
        return make(x), make(y)

    def _val_batch(self):
        x, y = self.val_loader.next_batch()
        make = lambda arr: jax.make_array_from_process_local_data(self.bshard, arr)
        return make(x), make(y)

    def validate(self) -> float:
        with self.tracer.span("eval", steps=self.cfg.val_steps):
            self.val_loader.reset()
            total = 0.0
            for _ in range(self.cfg.val_steps):
                x, y = self._val_batch()
                total += float(self.eval_step(self.params, x, y))
        return total / self.cfg.val_steps

    def run(self, max_steps: int | None = None, checkpoint_dir: str | None = None):
        cfg = self.cfg
        accum = cfg.grad_accum_steps
        tokens_per_step = cfg.total_batch_size
        last = min(max_steps if max_steps is not None else cfg.max_steps, cfg.max_steps)

        try:
            self._run_loop(last, accum, tokens_per_step, checkpoint_dir)
        except BaseException as e:
            # crash-time flight dump: the last N steps before death are
            # the artifact that matters (a DivergenceError path already
            # dumped with the non-finite reason; dump() is once-only)
            if self.sentinel is not None:
                self.sentinel.on_crash(e)
            raise
        finally:
            # join any in-flight async checkpoint write even when the loop
            # raises (a checkpoint must never outlive the process
            # half-written after save() reported success)
            if self._ckpt is not None:
                self._ckpt.wait()
        return self

    def _run_loop(self, last, accum, tokens_per_step, checkpoint_dir):
        cfg = self.cfg
        while self.step < last:
            step = self.step
            if step % cfg.val_every == 0 or step == last - 1:
                val_loss = self.validate()
                self.logger.val(step, val_loss)
                if self.sentinel is not None:
                    self.sentinel.record_event("val", step=step, loss=val_loss)
            if (
                self._sample_prompt_ids is not None
                and step % cfg.sample_every == 0
                and step > 0
            ):
                with self.tracer.span("sample", step=step):
                    self.sample()
            if checkpoint_dir and step > 0 and step % cfg.checkpoint_every == 0:
                self.save_checkpoint(checkpoint_dir)

            t0 = time.time()
            with self.tracer.span("data_load", step=step):
                x, y = self._global_batch(accum, self.train_loader)
            with self.tracer.span("train_step", step=step):
                out = self.train_step(self.params, self.opt_state, x, y)
                self.params, self.opt_state, loss, grad_norm = out[:4]
                jax.block_until_ready(loss)
            dt = time.time() - t0
            with self.tracer.span("train_log", step=step):
                self._log_step(step, out, loss, grad_norm, dt,
                               tokens_per_step)
            self.step += 1

    def _log_step(self, step, out, loss, grad_norm, dt, tokens_per_step):
        """The loop's tail after ``train_step`` (span ``train_log``): the
        scalar fetches, the logger's record, the sentinel."""
        # host scalars, fetched once: the logger and the sentinel both
        # consume these — the sentinel adds zero extra device syncs
        loss_f, grad_norm_f = float(loss), float(grad_norm)
        overflow = int(out[4]) if self._overflow_on else None
        tok_per_sec = tokens_per_step / dt
        mfu = mfu_hw = None
        if self._peak is not None:
            mfu = self._flops_per_token_model * tok_per_sec / self._peak
            mfu_hw = self._flops_per_token * tok_per_sec / self._peak
        self.logger.train_step(
            step, loss_f, float(self.schedule(step)), grad_norm_f,
            dt, tok_per_sec, mfu, mfu_hw,
        )
        if self.sentinel is not None and self.sentinel.observe_step(
            step, loss_f, grad_norm_f, overflow=overflow,
            step_ms=round(dt * 1000, 2),
        ):
            if self.cfg.telemetry.halt_on_divergence:
                where = (self.sentinel.dumped_to
                         or "written by process 0")  # non-master has
                raise DivergenceError(  # no dump path of its own
                    f"non-finite loss/grad_norm at step {step} "
                    f"(loss={loss_f}, grad_norm={grad_norm_f}); flight "
                    f"record: {where}"
                )

    def sample(self, num_return: int = 4, max_new_tokens: int = 32,
               top_k: int = 50):
        """Generate continuations like the reference's in-loop sampling
        (4 sequences x 32 tokens, top-k 50, train.py:170-175) — but with
        O(1) recurrent decode instead of full-prefix re-forwards."""
        import numpy as np

        from mamba_distributed_tpu.inference import generate

        prompt = jnp.asarray(self._sample_prompt_ids, jnp.int32)[None, :]
        prompt = jnp.tile(prompt, (num_return, 1))
        self.rng, key = jax.random.split(self.rng)
        out = generate(
            self.params, self.cfg.model, prompt, key,
            max_new_tokens=max_new_tokens, top_k=top_k,
        )
        if self.verbose:
            for row in np.asarray(out):
                text = (
                    self._decode_fn(row.tolist()) if self._decode_fn
                    else f"tokens {row.tolist()}"
                )
                print(f"sample: {text}")
        return out

    # --- checkpointing (training/checkpoint.py; full-state, exact resume;
    # async: the write overlaps the next training steps) ---

    def save_checkpoint(self, directory: str) -> None:
        from mamba_distributed_tpu.training.checkpoint import Checkpointer

        if self._ckpt is None or self._ckpt_dir != directory:
            if self._ckpt is not None:
                self._ckpt.close()
            self._ckpt = Checkpointer(directory)
            self._ckpt_dir = directory
        # the span covers the async dispatch (on-device snapshot), not the
        # background write — that's the cost the training loop actually pays
        with self.tracer.span("checkpoint_save", step=self.step):
            self._ckpt.save(
                self.step, self.params, self.opt_state,
                self.train_loader.state(), self.rng,
            )
        if self.sentinel is not None:
            self.sentinel.record_event("checkpoint_save", step=self.step)

    def finish(self) -> None:
        """Join any in-flight async checkpoint write (call before exit)."""
        if self._ckpt is not None:
            self._ckpt.close()
            self._ckpt = None

    def restore_checkpoint(self, directory: str, step: int | None = None) -> None:
        from mamba_distributed_tpu.training.checkpoint import restore_checkpoint

        if self._ckpt is not None:
            self._ckpt.wait()  # never restore past an uncommitted write

        self.step, self.params, self.opt_state, loader_state, self.rng = (
            restore_checkpoint(directory, self.params, self.opt_state, step)
        )
        self.train_loader.restore(loader_state)
        self.logger.preserve_history()
        self.tracer.preserve_history()
