"""The benchmark's own weights, made on the device from a seed.

One jitted call builds the whole tree in float32, in the layout the program's
entry points take (``embedding``, ``norm_f``, ``blocks`` stacked over the
Mamba-2 layers, ``attn_blocks`` stacked over the attention layers).  The
distributions are the published ones (mamba-ssm 2.2.2 ``_init_weights`` and
the Mamba-2 constructor): embedding N(0, 0.02); linear and depthwise-conv
weights U(+-1/sqrt(fan_in)); residual out-projections divided by
sqrt(n_layer); dt log-uniform in [dt_min, dt_max] through an inverse
softplus; A uniform in [1, 16] stored as its log; D and norm weights one.

Both sides of ``correct`` start from these: the program is handed the tree,
the reference reads it.  Nothing the program has made is read back.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.model import dims


def _uniform(key, shape, fan_in):
    bound = 1.0 / math.sqrt(fan_in)
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound)


def _mamba_block(key, m):
    s = dims(m)
    k = jax.random.split(key, 6)
    u = jax.random.uniform(k[3], (s["nh"],), jnp.float32)
    dt = jnp.exp(u * (math.log(m["dt_max"]) - math.log(m["dt_min"]))
                 + math.log(m["dt_min"]))
    dt = jnp.maximum(dt, m["dt_init_floor"])
    return {
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
    }


def _attn_block(key, m):
    s = dims(m)
    k = jax.random.split(key, 2)
    nh, nkv, hd = s["anh"], s["akv"], s["ahd"]
    return {
        "norm": {"weight": jnp.ones((s["d"],), jnp.float32)},
        "mixer": {
            "wqkv": {"kernel": _uniform(k[0], (s["d"], (nh + 2 * nkv) * hd), s["d"])},
            "out_proj": {"kernel": _uniform(k[1], (nh * hd, s["d"]), nh * hd)
                         / math.sqrt(s["n_layer"])},
        },
    }


def init_params(key, m: dict) -> dict:
    """The full float32 tree for the configuration's ``model`` dict ``m``."""
    s = dims(m)
    n_attn = len(s["attn_idx"])
    k_emb, k_m, k_a = jax.random.split(key, 3)
    params = {
        "embedding": m["initializer_range"] * jax.random.normal(
            k_emb, (s["vocab"], s["d"]), jnp.float32),
        "norm_f": {"weight": jnp.ones((s["d"],), jnp.float32)},
        "blocks": jax.vmap(lambda k: _mamba_block(k, m))(
            jax.random.split(k_m, s["n_layer"] - n_attn)),
    }
    if n_attn:
        params["attn_blocks"] = jax.vmap(lambda k: _attn_block(k, m))(
            jax.random.split(k_a, n_attn))
    return params


def seed_key(seed: int):
    """A PRNG key from ``--seed``, which may exceed 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
