"""Jitted train/eval steps with gradient accumulation.

One ``jax.jit`` covers the whole reference inner loop
(/root/reference/train.py:205-227): the micro-batch loop is a ``lax.scan``
over the leading accum axis, gradient averaging replaces DDP's allreduce
(XLA inserts the psum from the batch sharding), clip + AdamW update run
fused on-device.  Params/optimizer buffers are donated, so the step is
in-place at the HBM level.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from mamba_distributed_tpu.config import TrainConfig
from mamba_distributed_tpu.models import lm_loss
from mamba_distributed_tpu.models.lm import lm_loss_pipelined
from mamba_distributed_tpu.obs import scopes
from mamba_distributed_tpu.parallel.sharding import batch_sharding

# Python-side-effect trace counters (one bump per jit trace), same idiom
# as serving/engine.py — tests/test_obs.py pins that enabling host-side
# telemetry (spans + sentinels) leaves these unchanged.
TRACE_COUNTS = {"train_step": 0, "eval_step": 0}


def make_train_step(
    cfg: TrainConfig,
    optimizer: optax.GradientTransformation,
    mesh,
    params,
    opt_state,
    seq_ctx=None,
    overflow_threshold: float | None = None,
    freeze=None,
    params_map=None,
):
    """Build the compiled train step.

    Shardings are read off the already-placed ``params``/``opt_state`` so
    the step preserves them exactly (and donates the buffers).

    Returns ``step(params, opt_state, x, y) ->
    (params, opt_state, loss, grad_norm)`` with x/y (accum, B_global, T).

    ``overflow_threshold`` (TelemetryConfig) appends an int32 overflow
    flag to the outputs: 1 when the pre-clip global grad norm exceeds the
    threshold or is non-finite.  It is fused into the one existing jit —
    the sentinel's on-device half costs no extra trace and no extra
    launch; the host accumulates the flags into a counter
    (obs/sentinel.py).

    ``freeze`` (a pytree of bools matching ``params``; None = train
    everything, the exact status quo) splices the ORIGINAL frozen
    leaves back after ``apply_updates`` — the partial-fine-tune path
    (online LoRA tuning, serving/tuning/trainer.py).  The caller's
    masked optimizer (``optax.multi_transform`` + ``set_to_zero``)
    already produces zero updates for frozen leaves; the splice turns
    "adds 0.0" into "bit-identical" (a +0.0 rewrite would flip any
    -0.0 base weight's sign bit, breaking the frozen-base contract).

    ``params_map`` (pure tree->tree function; None = identity) is
    applied to the param tree INSIDE the loss, at trace time, before
    the forward.  Gradients flow through it to the original leaves,
    while anything it splices in (e.g. the constant adapter-id vector
    ``bind_adapter_ids`` adds for the LoRA delta path) stays a closed-
    over constant rather than a differentiated — and int-dtype —
    argument leaf.  Non-pipelined losses only (tuning never runs with
    ``mesh.pipe > 1``).
    """
    model_cfg = cfg.model

    def loss_fn(p, x, y):
        if params_map is not None:
            p = params_map(p)
        return lm_loss(p, model_cfg, x, y, seq_ctx=seq_ctx)

    pipe = cfg.mesh.pipe
    if pipe > 1 and model_cfg.loss_impl == "blocked":
        # lm_loss_pipelined runs the dense head; failing loudly beats
        # silently losing the memory saving the flag was set for
        raise NotImplementedError(
            "loss_impl='blocked' is not implemented for pipeline "
            "parallelism (mesh.pipe > 1) — use the dense loss there"
        )

    def step_fn(params, opt_state, x, y):
        TRACE_COUNTS["train_step"] += 1
        accum = x.shape[0]
        if pipe > 1:
            # GPipe: the accum microbatches stream through the pipeline
            # in ONE differentiable schedule — no lax.scan accumulation.
            # Composes with data parallelism: each (data, fsdp) replica
            # runs the schedule on its batch slice
            dp_axes = ("data", "fsdp") if cfg.data_parallel_size > 1 else None
            loss, grads = jax.value_and_grad(
                lambda p, x, y: lm_loss_pipelined(
                    p, model_cfg, x, y, mesh, batch_axes=dp_axes
                )
            )(params, x, y)
        elif accum == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, x[0], y[0])
        else:
            def micro(carry, xs):
                gsum, lsum = carry
                xb, yb = xs
                l, g = jax.value_and_grad(loss_fn)(params, xb, yb)
                with jax.named_scope(scopes.OPTIMIZER):  # accumulation
                    gsum = jax.tree.map(jnp.add, gsum, g)
                return (gsum, lsum + l), None

            zeros = jax.tree.map(jnp.zeros_like, params)
            (gsum, lsum), _ = jax.lax.scan(micro, (zeros, 0.0), (x, y))
            with jax.named_scope(scopes.OPTIMIZER):
                grads = jax.tree.map(lambda g: g / accum, gsum)
            loss = lsum / accum
        with jax.named_scope(scopes.OPTIMIZER):
            grad_norm = optax.global_norm(grads)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
        if freeze is not None:
            new_params = jax.tree.map(
                lambda frozen, new, old: old if frozen else new,
                freeze, new_params, params,
            )
        params = new_params
        if overflow_threshold is not None:
            overflow = jnp.int32(
                ~jnp.isfinite(grad_norm) | (grad_norm > overflow_threshold)
            )
            return params, opt_state, loss, grad_norm, overflow
        return params, opt_state, loss, grad_norm

    pshard = jax.tree.map(lambda a: a.sharding, params)
    oshard = jax.tree.map(lambda a: a.sharding, opt_state)
    bshard = batch_sharding(mesh, seq_sharded=seq_ctx is not None)
    # batches carry a leading (replicated) grad-accum axis
    ashard = NamedSharding(mesh, P(None, *bshard.spec))
    scalars = (None, None, None) if overflow_threshold is not None else (None, None)
    return jax.jit(
        step_fn,
        in_shardings=(pshard, oshard, ashard, ashard),
        out_shardings=(pshard, oshard, *scalars),
        donate_argnums=(0, 1),
    )


def make_eval_step(cfg: TrainConfig, mesh, params, seq_ctx=None):
    """Compiled loss-only step, x/y (B_global, T)."""
    model_cfg = cfg.model

    def eval_fn(params, x, y):
        TRACE_COUNTS["eval_step"] += 1
        return lm_loss(params, model_cfg, x, y, seq_ctx=seq_ctx)

    pshard = jax.tree.map(lambda a: a.sharding, params)
    bshard = batch_sharding(mesh, seq_sharded=seq_ctx is not None)
    return jax.jit(eval_fn, in_shardings=(pshard, bshard, bshard))
