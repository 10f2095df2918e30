"""The benchmark's own weights, made on the device from a seed.

One jitted call builds the whole tree in the dtype the configuration states,
in the layout the program's entry points take (``embedding``, ``norm_f``,
``blocks`` stacked over the Mamba-2 layers, ``attn_blocks`` stacked over the
attention layers).  A configuration's weights are *defined* as the seed's
float32 draw rounded once to that dtype: each layer is drawn in float32 from
its own key and rounded inside the loop that stacks it, so one layer's
float32 is alive at a time; ``draw_*`` hand the serving reference the same
layer from the same key.  The distributions are the published ones (mamba-ssm 2.2.2 ``_init_weights`` and
the Mamba-2 constructor): embedding N(0, 0.02); linear and depthwise-conv
weights U(+-1/sqrt(fan_in)); residual out-projections divided by
sqrt(n_layer); dt log-uniform in [dt_min, dt_max] through an inverse
softplus; A uniform in [1, 16] stored as its log; D and norm weights one.

Both sides of ``correct`` start from these: the program is handed the tree,
the reference draws the same values.  Nothing the program has made is read
back.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.model import dims


def _uniform(key, shape, fan_in):
    bound = 1.0 / math.sqrt(fan_in)
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound)


def cast(tree, dtype):
    """Every leaf of ``tree`` as ``dtype``."""
    return jax.tree.map(lambda a: a.astype(dtype), tree)


def draw_mamba_block(key, m, dtype):
    """One Mamba-2 block: the float32 draw from ``key``, rounded to ``dtype``."""
    s = dims(m)
    k = jax.random.split(key, 6)
    u = jax.random.uniform(k[3], (s["nh"],), jnp.float32)
    dt = jnp.exp(u * (math.log(m["dt_max"]) - math.log(m["dt_min"]))
                 + math.log(m["dt_min"]))
    dt = jnp.maximum(dt, m["dt_init_floor"])
    return cast({
        "norm": {"weight": jnp.ones((s["d"],), jnp.float32)},
        "mixer": {
            "in_proj": {"kernel": _uniform(k[0], (s["d"], s["d_in_proj"]), s["d"])},
            "conv": {"kernel": _uniform(k[1], (s["conv_dim"], s["w"]), s["w"]),
                     "bias": _uniform(k[2], (s["conv_dim"],), s["w"])},
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jax.random.uniform(
                k[4], (s["nh"],), jnp.float32, m["a_init_min"], m["a_init_max"])),
            "D": jnp.ones((s["nh"],), jnp.float32),
            "norm": {"weight": jnp.ones((s["di"],), jnp.float32)},
            "out_proj": {"kernel": _uniform(k[5], (s["di"], s["d"]), s["di"])
                         / math.sqrt(s["n_layer"])},
        },
    }, dtype)


def draw_attn_block(key, m, dtype):
    """One attention block: the float32 draw from ``key``, rounded to ``dtype``."""
    s = dims(m)
    k = jax.random.split(key, 2)
    nh, nkv, hd = s["anh"], s["akv"], s["ahd"]
    return cast({
        "norm": {"weight": jnp.ones((s["d"],), jnp.float32)},
        "mixer": {
            "wqkv": {"kernel": _uniform(k[0], (s["d"], (nh + 2 * nkv) * hd), s["d"])},
            "out_proj": {"kernel": _uniform(k[1], (nh * hd, s["d"]), nh * hd)
                         / math.sqrt(s["n_layer"])},
        },
    }, dtype)


def layer_keys(key, m: dict):
    """(the embedding's key, one key a Mamba-2 layer, one an attention layer)."""
    s = dims(m)
    n_attn = len(s["attn_idx"])
    k_emb, k_m, k_a = jax.random.split(key, 3)
    return (k_emb, jax.random.split(k_m, s["n_layer"] - n_attn),
            jax.random.split(k_a, n_attn) if n_attn else None)


def draw_embedding(key, m: dict, dtype):
    s = dims(m)
    return (m["initializer_range"] * jax.random.normal(
        key, (s["vocab"], s["d"]), jnp.float32)).astype(dtype)


def draw_norm_f(m: dict, dtype):
    return {"weight": jnp.ones((m["d_model"],), dtype)}


def init_params(key, m: dict, dtype="float32") -> dict:
    """The whole tree for the configuration's ``model`` dict ``m``, in
    ``dtype``; the stacked groups are filled a layer at a time."""
    k_emb, k_m, k_a = layer_keys(key, m)
    params = {
        "embedding": draw_embedding(k_emb, m, dtype),
        "norm_f": draw_norm_f(m, dtype),
        "blocks": jax.lax.map(lambda k: draw_mamba_block(k, m, dtype), k_m),
    }
    if k_a is not None:
        params["attn_blocks"] = jax.lax.map(
            lambda k: draw_attn_block(k, m, dtype), k_a)
    return params
