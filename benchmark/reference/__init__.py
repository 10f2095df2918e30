"""The plain references, one module for each architecture, found by a key of
the configuration's file.

``configs/<config>.json`` may state ``"reference": "<name>"``; the module is
then ``benchmark/reference/<name>.py`` (absent: ``mamba2``).  It is the one
place in the benchmark that knows the architecture, and the kinds, the control
and the rehearsal reach it only through ``of(config)``.  It imports nothing of
the program and is written from the published equations.

The contract: four duties.  ``m`` is the configuration's ``model`` dict,
``dtype`` its ``precision.params``.  All but the second are pure functions of
JAX arrays and plain Python values, traceable under ``jax.jit``: the caller
compiles them.

*weights* — ``init_params(key, m, dtype) -> tree``
    The configuration's weights in the layout the program's entry points
    take.  They are *defined* as the seed's float32 draw rounded once to
    ``dtype``.  The float32 draw of no more than one layer (of one stacked
    group) is alive at a time, and the rounding happens in the same compiled
    call, so the peak is the finished tree and one layer's float32.

*logits* — ``served_logits(key, m, dtype, ids, pos, precision, jit=jax.jit) -> (k, V) float32``
    The reference's logits at the positions ``pos`` (k,) of the one sequence
    ``ids`` (1, t), from the same key.  Called on the host: it walks the
    layers in Python and, for each, draws that layer's weights (rounded to
    ``dtype``, raised back to float32, so both sides start from the same
    numbers) in one compiled call and reads them in another, so that it never
    holds the tree and its peak does not grow with the depth.  Every program
    it compiles it compiles with ``jit`` and only hands their results on, so
    that ``rehearse.py`` can walk it over shapes for a described chip.
    ``precision`` is ``"f32"`` or the configuration's ``precision.control``
    (the control of ``correct``, never the reference).

*training* — ``loss_sum(params, m, ids, targets, precision) -> scalar`` and ``STACKED``
    The sum over every position of the cross-entropy, on a float32 tree of
    the layout above, and the names of the tree's top-level groups whose
    leaves are stacked over layers (``reference/train.py`` counts such a leaf
    once a layer and decays it by its per-layer rank).

*operations* — ``forward_flops_per_token(m, context)``, ``train_flops_per_token(m, seq_len)``
    Model convention: what the model defines, whatever implements it.
"""

from __future__ import annotations

import importlib

from benchmark.harness import Refused

DEFAULT = "mamba2"
DUTIES = {
    "weights": ("init_params",),
    "logits": ("served_logits",),
    "training": ("loss_sum", "STACKED"),
    "operations": ("forward_flops_per_token", "train_flops_per_token"),
}


def of(config: dict):
    """The reference module a configuration's file names, or refuse: a name
    with no module, or a module that lacks part of a duty."""
    name = config.get("reference", DEFAULT)
    try:
        mod = importlib.import_module("benchmark.reference." + name)
    except ModuleNotFoundError as e:
        raise Refused(f"the configuration names the reference {name!r}, and "
                      f"there is no benchmark/reference/{name}.py: {e}") from e
    for duty, names in DUTIES.items():
        lacking = [n for n in names if not hasattr(mod, n)]
        if lacking:
            raise Refused(f"benchmark/reference/{name}.py lacks {lacking} of "
                          f"the duty {duty!r} (benchmark/reference/__init__.py "
                          f"states the contract)")
    return mod


def params_dtype(config: dict) -> str:
    """The dtype the configuration's file states for its weights."""
    return config["precision"]["params"]


def freeze(m: dict) -> tuple:
    """A configuration dict as a hashable static argument."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in m.items()))


def seed_key(seed: int):
    """A PRNG key from ``--seed``, which may exceed 2**31."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
