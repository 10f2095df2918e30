"""The default reference: a Mamba-2 language model, some of whose layers may
be grouped-query attention (``attn_layer_idx``), with a tied head.

The four duties of ``benchmark/reference/__init__.py``: the weights are
``init.py``'s, the equations ``model.py``'s, the counts ``flops.py``'s; the
serving reference below is the one thing that needs both the draw and the
equations.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.flops import forward_flops_per_token, train_flops_per_token  # noqa: F401
from benchmark.reference import freeze, init, model
from benchmark.reference.init import init_params  # noqa: F401
from benchmark.reference.model import loss_sum  # noqa: F401

STACKED = ("blocks", "attn_blocks")


@functools.lru_cache(maxsize=None)
def _programs(m_items: tuple, dtype: str, precision: str, jit) -> dict:
    """The walk's programs, each compiled once a process: a layer's weights
    are drawn (rounded to ``dtype``, raised back to float32) in one program
    and read in another, which is then the program a scan over the whole
    tree's layers runs as its body, operation for operation."""
    m = dict(m_items)

    def keys(key):
        return init.layer_keys(key, m)

    def embedding(k):
        return init.draw_embedding(k, m, dtype).astype(jnp.float32)

    def embed(embedding, ids):
        return embedding[ids]

    def draw_mamba(ks, j):
        return init.cast(init.draw_mamba_block(ks[j], m, dtype), jnp.float32)

    def draw_attn(ks, j):
        return init.cast(init.draw_attn_block(ks[j], m, dtype), jnp.float32)

    def mamba(h, bp):
        return model.block(model.mamba2_mixer, bp, m, h, precision)

    def attn(h, bp):
        return model.block(model.attention_mixer, bp, m, h, precision)

    def head(h, embedding, pos):
        w = init.draw_norm_f(m, dtype)["weight"].astype(jnp.float32)
        return model.mm(model.rms_norm(h[0, pos], w, m["norm_eps"]),
                        embedding.T, precision)

    return {f.__name__: jit(f) for f in (keys, embedding, embed, draw_mamba,
                                         draw_attn, mamba, attn, head)}


def served_logits(key, m: dict, dtype, ids, pos, precision="f32", jit=jax.jit):
    """ids (1, t), pos (k,) -> float32 logits (k, V) at ``pos``.  Only the
    embedding, which the tied head reads again, lives from the first layer to
    the last; of the layers one's weights are alive at a time."""
    p = _programs(freeze(m), dtype, precision, jit)
    k_emb, k_m, k_a = p["keys"](key)
    embedding = p["embedding"](k_emb)
    h = p["embed"](embedding, ids)
    attn_idx = set(m.get("attn_layer_idx", ()))
    i_mamba = i_attn = 0
    for i in range(m["n_layer"]):
        if i in attn_idx:
            h = p["attn"](h, p["draw_attn"](k_a, i_attn))
            i_attn += 1
        else:
            h = p["mamba"](h, p["draw_mamba"](k_m, i_mamba))
            i_mamba += 1
    return p["head"](h, embedding, pos)
