"""The reference's first training steps: loss, gradient, AdamW — float32.

Follows the source's recipe as the configuration file states it (``train``
group): the mean cross-entropy over every token of the step (all rows of all
accumulation micro-batches), clipping by the global norm, AdamW with bias
correction and decoupled decay on matrices only (per-layer ndim >= 2), the
warm-up learning rate ``max_lr * (step + 1) / warmup_steps``.  Rows are taken
``row_block`` at a time and their gradients summed, so that the step fits
beside nothing else on one chip.

What is compared with the program (``compare``): each step's loss; per leaf —
a stacked block leaf counts once per layer — the norm of the first clipped
gradient and the norm of the parameters' change after the steps.

Nothing here knows an architecture: the loss and the names of the tree's
stacked groups are the configuration's reference module's (``ref``, found by
``benchmark.reference.of``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import freeze, seed_key


# ------------------------------------------------------------ leaves


def _path_name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def leaf_norms(tree, stacked) -> dict:
    """{leaf name: norms}: one norm per layer for a leaf of the groups
    ``stacked`` (shape (L,)), one for any other (shape ()).  Traceable."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = _path_name(path)
        x = leaf.astype(jnp.float32)
        if name.split("/")[0] in stacked:
            out[name] = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
        else:
            out[name] = jnp.sqrt(jnp.sum(x * x))
    return out


def flat_norms(norms: dict) -> dict:
    """{"name[i]": float} from ``leaf_norms`` output fetched to the host."""
    out = {}
    for name, v in norms.items():
        v = np.asarray(v, np.float64)
        if v.ndim == 0:
            out[name] = float(v)
        else:
            for i, x in enumerate(v):
                out[f"{name}[{i}]"] = float(x)
    return out


def decay_mask(tree, stacked):
    """True where the recipe decays: matrices, per layer."""
    def one(path, leaf):
        layered = _path_name(path).split("/")[0] in stacked
        return leaf.ndim - (1 if layered else 0) >= 2
    return jax.tree_util.tree_map_with_path(one, tree)


# ------------------------------------------------------------ steps


def learning_rate(step: int, t: dict) -> float:
    """The warm-up branch of the schedule; the compared steps are 0, 1, 2."""
    if step >= t["warmup_steps"]:
        raise ValueError("the reference follows warm-up steps only")
    return t["max_lr"] * (step + 1.0) / t["warmup_steps"]


@functools.partial(jax.jit, static_argnames=("ref", "m_items", "precision"))
def _block_grad(params, ids, targets, ref, m_items, precision):
    return jax.value_and_grad(ref.loss_sum)(
        params, dict(m_items), ids, targets, precision)


@jax.jit
def _tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


def spread_rows(devices):
    """(sharding of a block's rows, sharding of the weights) over several
    chips, or (None, None) on one: the reference of a four-chip cell takes
    each block's rows a quarter to a chip, so that it costs the time of a
    one-chip cell's.  The sum over rows is the compiler's to exchange."""
    if devices is None or len(devices) < 2:
        return None, None
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(devices), ("rows",))
    return NamedSharding(mesh, P("rows")), NamedSharding(mesh, P())


def step_gradient(ref, params, m, x, y, precision="f32", row_block=4,
                  rows=None, devices=None):
    """Mean loss and its gradient over a step's rows.  x, y (accum, B, T)
    int arrays.  ``rows`` (a slice of the flattened accum*B rows) plants the
    faults "part of the batch left out, the mean taken over the rest"."""
    ids = np.asarray(x).reshape(-1, x.shape[-1])
    tgt = np.asarray(y).reshape(-1, y.shape[-1])
    if rows is not None:
        ids, tgt = ids[rows], tgt[rows]
    key = freeze(m)
    by_rows, _ = spread_rows(devices)
    put = (lambda a: jax.device_put(a, by_rows)) if by_rows is not None \
        else jnp.asarray
    total, grads = 0.0, None
    for lo in range(0, ids.shape[0], row_block):
        l, g = _block_grad(params, put(ids[lo:lo + row_block]),
                           put(tgt[lo:lo + row_block]), ref, key, precision)
        total = total + l
        grads = g if grads is None else _tree_add(grads, g)
    n = ids.size
    return total / n, jax.tree.map(lambda g: g / n, grads)


@functools.partial(jax.jit, donate_argnums=(0, 2, 3), static_argnames=(
    "stacked", "b1", "b2", "eps", "wd", "clip"))
def _adamw(params, grads, mu, nu, lr, count, *, stacked, b1, b2, eps, wd, clip):
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.where(gnorm < clip, 1.0, clip / gnorm)
    g = jax.tree.map(lambda x: x * scale, grads)
    mu = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, mu, g)
    nu = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, nu, g)
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count
    mask = decay_mask(params, stacked)

    def upd(p, a, b, decays):
        u = (a / c1) / (jnp.sqrt(b / c2) + eps)
        return p - lr * (u + (wd * p if decays else 0.0))

    params = jax.tree.map(upd, params, mu, nu, mask)
    return params, mu, nu, gnorm, leaf_norms(g, stacked)


def initial_params(ref, seed: int, m: dict, dtype):
    """The configuration's weights as the reference's steps read them: the
    tree of ``--seed`` in ``dtype``, raised back to float32."""
    return jax.jit(lambda k: jax.tree.map(
        lambda a: a.astype(jnp.float32), ref.init_params(k, m, dtype)))(
            seed_key(seed))


def first_steps(ref, params0, m, t, batches, precision="f32", row_block=4,
                rows=None, frozen=False, devices=None):
    """Run ``len(batches)`` steps of the reference module ``ref``'s loss from
    the float32 tree ``params0`` (not consumed).

    Returns {"losses": [...], "grad_norm": first global norm, "grad": flat
    per-leaf norms of the first clipped gradient, "delta": flat per-leaf norms
    of the change after the steps}.  ``frozen`` plants the fault "a step that
    returns its state unchanged".
    """
    _, everywhere = spread_rows(devices)
    if everywhere is not None:
        params0 = jax.device_put(params0, everywhere)
    params = jax.tree.map(jnp.copy, params0)
    mu = jax.tree.map(jnp.zeros_like, params0)
    nu = jax.tree.map(jnp.zeros_like, params0)
    out = {"losses": []}
    for step, (x, y) in enumerate(batches):
        loss, grads = step_gradient(ref, params, m, x, y, precision, row_block,
                                    rows, devices)
        out["losses"].append(float(loss))
        # _adamw consumes its parameters; a frozen step keeps them
        new, mu, nu, gnorm, gleaf = _adamw(
            jax.tree.map(jnp.copy, params) if frozen else params, grads, mu, nu, learning_rate(step, t), step + 1.0,
            stacked=tuple(ref.STACKED), b1=t["adam_b1"], b2=t["adam_b2"], eps=t["adam_eps"],
            wd=t["weight_decay"], clip=t["grad_clip"])
        if step == 0:
            out["grad_norm"] = float(gnorm)
            out["grad"] = flat_norms(gleaf)
        params = params if frozen else new
    delta = jax.jit(lambda a, b: leaf_norms(
        jax.tree.map(jnp.subtract, a, b), ref.STACKED))(params, params0)
    out["delta"] = flat_norms(delta)
    return out


# ------------------------------------------------------------ comparison


def worst_leaf_gap(prog: dict, ref: dict, skip=()) -> tuple[float, str]:
    """The widest gap between the program's norm and the reference's, over
    the leaves, measured against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    median = float(np.median(list(ref.values())))
    worst, where = 0.0, ""
    for name, r in ref.items():
        if name in skip:
            continue
        gap = abs(prog[name] - r) / max(r, median)
        if gap > worst:
            worst, where = gap, name
    return worst, where


def compare(prog: dict, ref: dict) -> dict:
    """{number: value} of what is compared; ``prog`` and ``ref`` as
    ``first_steps`` returns them.  Leaves whose reference gradient is under a
    thousandth of the median leaf's move under Adam by round-off alone and are
    left out of the change."""
    med = float(np.median(list(ref["grad"].values())))
    dead = {k for k, v in ref["grad"].items() if v < 1e-3 * med}
    n = min(len(prog["losses"]), len(ref["losses"]))
    grad_gap, grad_leaf = worst_leaf_gap(prog["grad"], ref["grad"])
    delta_gap, delta_leaf = worst_leaf_gap(prog["delta"], ref["delta"], dead)
    return {
        "loss_gap": max(abs(a - b) for a, b in
                        zip(prog["losses"][:n], ref["losses"][:n])),
        "grad_gap": grad_gap, "delta_gap": delta_gap,
        "_where": {"grad_gap": grad_leaf, "delta_gap": delta_leaf,
                   "dead_leaves": len(dead)},
    }
