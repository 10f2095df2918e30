"""Pipeline parallelism over the layer stack.

Neither the reference nor any BASELINE configuration uses pipeline
parallelism (SURVEY.md §2.3 lists it "out of scope"); it is part of the
framework's full parallelism menu.  The trainer wires it in whenever the
mesh has a ``pipe`` axis > 1 (training/train_step.py builds the train
step around :func:`pipelined_layers`, composing with data parallelism).

TPU-idiomatic formulation: the scan-over-layers parameter stack is
sharded on its *layer* axis over a ``stage`` mesh axis, and a GPipe-style
schedule runs as a ``lax.scan`` over clock ticks inside ``shard_map``.
At tick t, stage s runs its local layers on the activation of microbatch
``t - s`` (bubble ticks compute on garbage and are masked out — uniform
compute, no divergent control flow, which is what the TPU wants), then
``ppermute``s the activation to stage s+1.  Total ticks =
``n_micro + n_stages - 1``; bubble fraction ``(S-1)/T`` exactly as in
the GPipe paper.

The schedule is exact: outputs equal running every layer locally
(tests/test_pipeline.py pins equality on the virtual mesh, including the
real Mamba-2 block body with its (hidden, residual) carry).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def _tree_where(pred, a, b):
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def pipelined_layers(
    body_fn: Callable,
    stacked_params,
    xs,
    mesh: Mesh,
    axis: str = "stage",
    batch_axes=None,
):
    """Run ``scan(body_fn)`` over layer-stacked params, pipelined over
    ``axis``.

    Args:
      body_fn: ``(activation, layer_params) -> activation`` — one layer.
        The activation may be any pytree of arrays (e.g. the block
        pipeline's (hidden, residual) pair).
      stacked_params: pytree whose leaves carry a leading ``n_layer``
        axis; n_layer % n_stages must be 0 (sharded over ``axis``).
      xs: activation pytree whose leaves carry a leading (n_micro, ...)
        microbatch axis.
      mesh: mesh containing ``axis``.
      batch_axes: optional mesh axis name(s) the activations' dim 1 (the
        batch dim under the microbatch axis) is sharded over — this is
        how pipeline parallelism composes with data parallelism: each
        data replica runs the same GPipe schedule on its batch slice,
        and params stay replicated across ``batch_axes`` (their gradient
        psum over the data axes happens in the surrounding GSPMD
        program / shard_map transpose).  None = replicated activations.

    Returns the output pytree with the same (n_micro, ...) leading axis —
    identical to an unpipelined ``lax.scan`` of ``body_fn`` over all
    layers for each microbatch.
    """
    n_stages = mesh.shape[axis]
    n_layer = jax.tree.leaves(stacked_params)[0].shape[0]
    if n_layer % n_stages != 0:
        raise ValueError(
            f"pipelined_layers: n_layer ({n_layer}) must divide evenly "
            f"over the {n_stages} pipeline stages of mesh axis {axis!r}"
        )
    n_micro = jax.tree.leaves(xs)[0].shape[0]
    n_ticks = n_micro + n_stages - 1

    def local(params_local, xs_local):
        s = jax.lax.axis_index(axis)
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        def run_stage(act):
            def layer(carry, p):
                return body_fn(carry, p), None

            out, _ = jax.lax.scan(layer, act, params_local)
            return out

        buf = jax.tree.map(lambda x: jnp.zeros_like(x[0]), xs_local)
        outs = jax.tree.map(jnp.zeros_like, xs_local)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t while t < n_micro
            inject = jax.tree.map(
                lambda x: x[jnp.clip(t, 0, n_micro - 1)], xs_local
            )
            take_inject = jnp.logical_and(s == 0, t < n_micro)
            buf = _tree_where(take_inject, inject, buf)
            y = run_stage(buf)
            # the last stage finished microbatch m = t - (S-1) this tick
            m = t - (n_stages - 1)
            write = jnp.logical_and(s == n_stages - 1, m >= 0)
            idx = jnp.clip(m, 0, n_micro - 1)
            outs = jax.tree.map(
                lambda o, y_leaf: jax.lax.dynamic_update_index_in_dim(
                    o, jnp.where(write, y_leaf, o[idx]), idx, axis=0
                ),
                outs,
                y,
            )
            # activations advance one stage per tick
            buf = jax.lax.ppermute(y, axis, perm) if perm else y
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(n_ticks))
        # only the last stage holds real outputs; share them with everyone
        outs = jax.tree.map(
            lambda o: jax.lax.psum(
                jnp.where(s == n_stages - 1, o, jnp.zeros_like(o)), axis
            ),
            outs,
        )
        return outs

    # params shard their leading layer axis over the stage axis; activations
    # are replicated on it (and batch-sharded over batch_axes if given)
    param_specs = jax.tree.map(
        lambda p: P(axis, *(None,) * (jnp.ndim(p) - 1)), stacked_params
    )
    if batch_axes is None:
        xs_specs = jax.tree.map(lambda x: P(*(None,) * jnp.ndim(x)), xs)
    else:
        xs_specs = jax.tree.map(
            lambda x: P(None, batch_axes, *(None,) * (jnp.ndim(x) - 2)), xs
        )
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(param_specs, xs_specs),
        out_specs=xs_specs,
        check_vma=False,
    )
    return fn(stacked_params, xs)


def pipelined_decode_layers(
    body_fn: Callable,
    stacked_params,
    stacked_state,
    act,
    mesh: Mesh,
    axis: str = "stage",
    n_micro: int | None = None,
):
    """One STATEFUL decode sub-step over the layer stack, GPipe-
    pipelined over ``axis`` with per-stage state residency — the
    serving tick's microbatched launch (docs/SERVING.md "3-D serving
    mesh").

    Where :func:`pipelined_layers` pipelines a stateless layer body
    over a microbatch axis the caller supplies, this variant owns the
    serving decode shape: the batch is a LANE axis (slots of the
    serving pool — independent streams, so lanes are the legal
    microbatch unit; consecutive tokens of one lane are sequentially
    dependent and can never pipeline), and every layer carries per-lane
    recurrent state that must stay resident on the stage that owns the
    layer.  ``stacked_state`` leaves are (L, S, ...) — layer-stacked,
    lane-indexed on axis 1 — sharded over ``axis`` on the layer axis
    exactly like ``stacked_params`` (parallel/sharding.slot_pool_specs
    at ``stage_shards > 1``), so state never crosses stages: at tick
    ``t`` stage ``s`` dynamic-slices the lane block of microbatch
    ``m = t - s`` out of its OWN state rows, runs its local layers, and
    writes the advanced rows back in place (bubble ticks — ``m``
    outside [0, n_micro) — write the old rows back unchanged, the
    tree-where masking of ``pipelined_layers`` applied to state).

    Args:
      body_fn: ``(act, layer_params, layer_state) -> (act, new_state)``
        — one decode-step layer on one lane block.  ``act`` may be any
        pytree (e.g. the block pipeline's (hidden, residual) pair);
        leaves carry a leading lane axis.
      stacked_params: pytree, leaves (L, ...); L % n_stages == 0.
      stacked_state: pytree, leaves (L, S, ...) — same L, lane axis 1.
      act: activation pytree, leaves (S, ...) — ALL lanes (the caller's
        post-embedding activations); split into ``n_micro`` contiguous
        lane blocks of width S / n_micro here.
      mesh: mesh containing ``axis``.
      n_micro: microbatch count (default ``n_stages``); S % n_micro
        must be 0.  The schedule runs ``n_micro + n_stages - 1`` clock
        ticks — bubble fraction ``(n_stages - 1) / n_ticks`` exactly as
        in the GPipe paper, so more microbatches amortize the fill/
        drain cost while n_micro = 1 degenerates to sequential stages.

    Returns ``(act_out, new_stacked_state)`` — bitwise identical to an
    unpipelined ``lax.scan`` of ``body_fn`` over all layers (each
    lane's op sequence is unchanged; the schedule only reorders WHICH
    (layer, lane-block) cell runs when, and float ops are oblivious to
    that) — pinned by tests/test_pipeline_serving.py with the real
    Mamba decode-step body.
    """
    n_stages = mesh.shape[axis]
    n_layer = jax.tree.leaves(stacked_params)[0].shape[0]
    if n_layer % n_stages != 0:
        raise ValueError(
            f"pipelined_decode_layers: n_layer ({n_layer}) must divide "
            f"evenly over the {n_stages} pipeline stages of mesh axis "
            f"{axis!r}"
        )
    n_lanes = jax.tree.leaves(act)[0].shape[0]
    if n_micro is None:
        n_micro = n_stages
    if n_lanes % n_micro != 0:
        raise ValueError(
            f"pipelined_decode_layers: lane count ({n_lanes}) must "
            f"divide over n_micro ({n_micro}) microbatches"
        )
    mw = n_lanes // n_micro
    n_ticks = n_micro + n_stages - 1

    def local(params_local, state_local, act_in):
        s = jax.lax.axis_index(axis)
        perm = [(i, i + 1) for i in range(n_stages - 1)]
        xs = jax.tree.map(
            lambda x: x.reshape((n_micro, mw) + x.shape[1:]), act_in
        )
        buf = jax.tree.map(lambda x: jnp.zeros_like(x[0]), xs)
        outs = jax.tree.map(jnp.zeros_like, xs)

        def layer(carry, xs_):
            bp, st = xs_
            return body_fn(carry, bp, st)

        def tick(carry, t):
            buf, outs, state_local = carry
            # stage 0 ingests microbatch t while t < n_micro
            inject = jax.tree.map(
                lambda x: x[jnp.clip(t, 0, n_micro - 1)], xs
            )
            take_inject = jnp.logical_and(s == 0, t < n_micro)
            buf = _tree_where(take_inject, inject, buf)
            # this stage works microbatch m = t - s (clipped: bubble
            # ticks compute on garbage lanes, masked below)
            m = t - s
            midx = jnp.clip(m, 0, n_micro - 1)
            st_m = jax.tree.map(
                lambda v: jax.lax.dynamic_slice_in_dim(
                    v, midx * mw, mw, axis=1
                ),
                state_local,
            )
            y, new_st = jax.lax.scan(layer, buf, (params_local, st_m))
            # state residency: the advanced rows write back into this
            # stage's own slice; bubble ticks re-write the OLD rows
            # (read-modify-write of identical values — a masked no-op)
            valid = jnp.logical_and(m >= 0, m < n_micro)
            write_st = _tree_where(valid, new_st, st_m)
            state_local = jax.tree.map(
                lambda v, w: jax.lax.dynamic_update_slice_in_dim(
                    v, w, midx * mw, axis=1
                ),
                state_local,
                write_st,
            )
            # the last stage finished microbatch m this tick
            write = jnp.logical_and(s == n_stages - 1, m >= 0)
            outs = jax.tree.map(
                lambda o, y_leaf: jax.lax.dynamic_update_index_in_dim(
                    o, jnp.where(write, y_leaf, o[midx]), midx, axis=0
                ),
                outs,
                y,
            )
            buf = jax.lax.ppermute(y, axis, perm) if perm else y
            return (buf, outs, state_local), None

        (buf, outs, state_local), _ = jax.lax.scan(
            tick, (buf, outs, state_local), jnp.arange(n_ticks)
        )
        # only the last stage holds real outputs; share them with
        # everyone (state stays put — each stage returns its own rows)
        outs = jax.tree.map(
            lambda o: jax.lax.psum(
                jnp.where(s == n_stages - 1, o, jnp.zeros_like(o)), axis
            ),
            outs,
        )
        act_out = jax.tree.map(
            lambda o: o.reshape((n_lanes,) + o.shape[2:]), outs
        )
        return act_out, state_local

    param_specs = jax.tree.map(
        lambda p: P(axis, *(None,) * (jnp.ndim(p) - 1)), stacked_params
    )
    state_specs = jax.tree.map(
        lambda v: P(axis, *(None,) * (jnp.ndim(v) - 1)), stacked_state
    )
    act_specs = jax.tree.map(lambda x: P(*(None,) * jnp.ndim(x)), act)
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(param_specs, state_specs, act_specs),
        out_specs=(act_specs, state_specs),
        check_vma=False,
    )
    return fn(stacked_params, stacked_state, act)
