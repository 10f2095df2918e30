"""Plain float32 reference of granite-4.0-h-small (ibm-granite,
``granitemoehybrid``): Mamba-2 layers and NoPE attention layers, each followed
by routed experts beside a shared expert.

Written from the equations of the published implementation (HF
``transformers`` ``modeling_granitemoehybrid.py``) and the values of the
published ``config.json``; it imports nothing of the program.

    h = E[ids] * embedding_multiplier
    every layer l (attention where l is in attn_layer_idx, else Mamba-2):
      u = RMSNorm(h)
      mamba:     m = Mamba2(u)          z | xBC | dt, conv + SiLU, the SSD
                                        recurrence, RMSNorm(y * silu(z)) over
                                        all channels of the one group, W_out
      attention: m = softmax(q k^T * attention_multiplier, causal) v W_o
                                        grouped queries, NO rotary (NoPE)
      h = h + m * residual_multiplier
      f = RMSNorm(h)
      r = f W_r                         float32, all moe_num_experts wide
      (v_j, e_j) = top_k(r, moe_top_k);  g = softmax(v_1..v_k)   the k alone
      routed = sum over those j whose e_j is HELD of g_j * W2[e_j](b_j * silu(a_j)),
               [b_j | a_j] = f W1[e_j]
      shared = Ws2(b * silu(a)),  [b | a] = f Ws1
      h = h + (routed + shared) * residual_multiplier
    logits = (RMSNorm(h) E^T) * lm_head_multiplier            the head is tied

The HELD experts are ``moe_experts_held`` of them from ``moe_first_expert``
on (none stated: all): the chip's share of a deployment whose other experts
live elsewhere.  The terms of the absent experts are left out here as in the
program, and the partial sum goes on to the next layer; nothing stands in for
them.  The expert layer is the plainest loop there is: for each held expert,
every row, times a gate that is zero where the expert was not among the row's
``moe_top_k``.

``m`` is the configuration's ``model`` dict under the program's key names.
The weights' layout is the program's: two stacks, ``blocks`` (the Mamba-2
layers) and ``attn_blocks``; a gated MLP's first matrix holds ``[W_up |
W_gate]`` side by side (the SiLU takes the SECOND half; the published model
gates with the first half of ``input_linear``), the experts' ``moe.w1`` /
``moe.w2`` are stacked over the HELD experts, the router ``moe.router`` keeps
all ``moe_num_experts`` columns, the shared expert is ``shared.fc1`` /
``shared.fc2``.  An expert's weights are its own key's draw whatever the share
held, so two shares of one seed are two parts of one model.  What the
published config does not state (the draws) follows the repository's other
references (mamba-ssm's constructors) and is listed under ``assumed`` in the
configuration's file.

The four duties of ``benchmark/reference/__init__.py`` are below.  The
embedding (411 M values at the published vocabulary) is drawn and read in row
blocks, so that no more than one block's float32 is alive.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference import freeze
from benchmark.reference.init import _uniform, cast
from benchmark.reference import model
from benchmark.reference.model import HIGHEST, dims as mamba_dims, rms_norm

STACKED = ("blocks", "attn_blocks")
VOCAB_BLOCKS = 8  # the embedding is drawn and read in this many row blocks

# Beside "f32" and the control's "fp8", ``precision`` may name a planted
# FAULT: the float32 reference with one part of the expert layer wrong, put
# in the program's place by ``benchmark/control.py --control <fault>`` and
# the tests, which must read ``correct`` false through the run's own judge.
FAULTS = (
    "fault_capacity",   # an expert takes its first k * rows / E rows and drops the rest
    "fault_gate_all",   # a softmax over all the router's logits, not renormalised
    "fault_no_shared",  # the shared expert left out
)


def products(precision: str) -> str:
    """The precision of the matrix products under ``precision``."""
    return "f32" if precision in FAULTS else precision


def mm(x, w, precision: str):
    return model.mm(x, w, products(precision))


# ------------------------------------------------------------ sizes


def held(m: dict) -> tuple[int, int]:
    """(first, count) of the routed experts held here."""
    n = m.get("moe_experts_held", 0)
    return (m.get("moe_first_expert", 0), n) if n else (0, m["moe_num_experts"])


def is_attn(m: dict, i: int) -> bool:
    return i in set(m["attn_layer_idx"])


# ------------------------------------------------------------ weights


def _draw_expert_layer(key, m: dict) -> dict:
    """norm2, the router, the held experts and the shared expert of one
    layer, float32."""
    d, ff, sf, E = (m["d_model"], m["d_intermediate"],
                    m["moe_shared_intermediate"], m["moe_num_experts"])
    first, n = held(m)
    k_r, k_e, k_s1, k_s2 = jax.random.split(key, 4)

    def expert(k):
        k1, k2 = jax.random.split(k)
        return _uniform(k1, (d, 2 * ff), d), _uniform(k2, (ff, d), ff)

    w1, w2 = jax.lax.map(expert, jax.random.split(k_e, E)[first:first + n])
    return {
        "norm2": {"weight": jnp.ones((d,), jnp.float32)},
        "moe": {"router": {"kernel": _uniform(k_r, (d, E), d)},
                "w1": w1, "w2": w2},
        "shared": {"fc1": {"kernel": _uniform(k_s1, (d, 2 * sf), d)},
                   "fc2": {"kernel": _uniform(k_s2, (sf, d), sf)}},
    }


def draw_mamba_block(key, m: dict, dtype):
    """One Mamba-2 layer: the float32 draw from ``key``, rounded to
    ``dtype``.  No depth rescale of the out-projection:
    ``residual_multiplier`` stands in its place."""
    s = mamba_dims(m)
    k = jax.random.split(key, 7)
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
            "out_proj": {"kernel": _uniform(k[5], (s["di"], s["d"]), s["di"])},
        },
        **_draw_expert_layer(k[6], m),
    }, dtype)


def draw_attn_block(key, m: dict, dtype):
    """One attention layer: the float32 draw from ``key``, rounded."""
    d = m["d_model"]
    nh, nkv, hd = m["attn_num_heads"], m["attn_num_kv_heads"], m["attn_head_dim"]
    k = jax.random.split(key, 3)
    return cast({
        "norm": {"weight": jnp.ones((d,), jnp.float32)},
        "mixer": {
            "wqkv": {"kernel": _uniform(k[0], (d, (nh + 2 * nkv) * hd), d)},
            "out_proj": {"kernel": _uniform(k[1], (nh * hd, d), nh * hd)},
        },
        **_draw_expert_layer(k[2], m),
    }, dtype)


def layer_keys(key, m: dict):
    """(the embedding's key, a key a Mamba-2 layer, a key an attention layer)."""
    n_attn = len(m["attn_layer_idx"])
    k_emb, k_m, k_a = jax.random.split(key, 3)
    return (k_emb, jax.random.split(k_m, m["n_layer"] - n_attn),
            jax.random.split(k_a, n_attn))


def _vocab_blocks(m: dict) -> int:
    return VOCAB_BLOCKS if m["vocab_size"] % VOCAB_BLOCKS == 0 else 1


def embedding_block(key, m: dict, dtype):
    """(V / blocks, d): one block of the embedding's rows, rounded to
    ``dtype``: N(0, initializer_range / embedding_multiplier), so that the
    stream starts at the usual ``initializer_range`` once the rows are
    multiplied.  Drawn at ``initializer_range`` itself and multiplied by 12,
    a seed's rows would swamp what the layers add, and the tied head would
    answer every position with its own input token (a logit of 2.7 against
    0.35 for the best of the rest): no comparison of logits could then tell
    a right program from a wrong one."""
    return (m["initializer_range"] / m["embedding_multiplier"]
            * jax.random.normal(
        key, (m["vocab_size"] // _vocab_blocks(m), m["d_model"]),
        jnp.float32)).astype(dtype)


def draw_embedding(key, m: dict, dtype):
    rows = jax.lax.map(lambda k: embedding_block(k, m, dtype),
                       jax.random.split(key, _vocab_blocks(m)))
    return rows.reshape(m["vocab_size"], m["d_model"])


def draw_norm_f(m: dict, dtype):
    return {"weight": jnp.ones((m["d_model"],), dtype)}


def init_params(key, m: dict, dtype="float32") -> dict:
    """The whole tree in ``dtype``; each stack is filled a layer at a time."""
    k_emb, k_m, k_a = layer_keys(key, m)
    return {
        "embedding": draw_embedding(k_emb, m, dtype),
        "norm_f": draw_norm_f(m, dtype),
        "blocks": jax.lax.map(lambda k: draw_mamba_block(k, m, dtype), k_m),
        "attn_blocks": jax.lax.map(lambda k: draw_attn_block(k, m, dtype), k_a),
    }


# ------------------------------------------------------------ layers


def attention(p, m: dict, u, precision, q_block: int = 256):
    """Causal grouped-query attention with NO rotary embedding and the
    stated softmax scale.  Query rows are taken ``q_block`` at a time, so
    that the (t, t) scores never exist whole."""
    b, t, _ = u.shape
    nh, nkv, hd = m["attn_num_heads"], m["attn_num_kv_heads"], m["attn_head_dim"]
    qkv = mm(u, p["wqkv"]["kernel"], precision)
    q = qkv[..., :nh * hd].reshape(b, t, nh, hd)
    k = qkv[..., nh * hd:(nh + nkv) * hd].reshape(b, t, nkv, hd)
    v = qkv[..., (nh + nkv) * hd:].reshape(b, t, nkv, hd)
    rep = nh // nkv
    tq = -(-t // q_block) * q_block
    qp = jnp.pad(q, ((0, 0), (0, tq - t), (0, 0), (0, 0)))
    qb = qp.reshape(b, tq // q_block, q_block, nkv, rep, hd).swapaxes(0, 1)
    iq = jnp.arange(tq).reshape(tq // q_block, q_block)
    s_idx = jnp.arange(t)

    def block(args):
        qq, ib = args  # (b, q, nkv, rep, hd), (q,)
        sc = jnp.einsum("bqgrh,bkgh->bgrqk", qq, k, precision=HIGHEST)
        sc = sc * m["attention_multiplier"]
        sc = jnp.where(s_idx[None, :] <= ib[:, None], sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bgrqk,bkgh->bqgrh", w, v, precision=HIGHEST)

    o = jax.lax.map(block, (qb, iq)).swapaxes(0, 1)
    o = o.reshape(b, tq, nh * hd)[:, :t]
    return mm(o, p["out_proj"]["kernel"], precision)


def gated_mlp(w1, w2, x, precision):
    """``W2(up * silu(gate))`` with ``[up | gate] = x W1``."""
    ug = mm(x, w1, precision)
    ff = w2.shape[0]
    return mm(ug[..., :ff] * jax.nn.silu(ug[..., ff:]), w2, precision)


def gates(p, m: dict, f, precision="f32"):
    """(rows, held): a row's gate for each held expert; zero where the
    expert is not among the row's ``moe_top_k``.  The router's product is
    float32 whatever ``precision`` the other products take: the
    configuration states it so."""
    first, n = held(m)
    k, E = m["moe_top_k"], m["moe_num_experts"]
    r = mm(f, p["router"]["kernel"], "f32")
    top_v, top_e = jax.lax.top_k(r, k)
    g = jax.nn.softmax(top_v, axis=-1)  # over the chosen alone
    if precision == "fault_gate_all":
        g = jnp.take_along_axis(jax.nn.softmax(r, axis=-1), top_e, axis=-1)
    e = first + jnp.arange(n)
    g = jnp.sum(jnp.where(top_e[..., None] == e, g[..., None], 0.0), axis=-2)
    if precision == "fault_capacity":
        cap = max(1, -(-k * f.shape[0] // E))  # the mean load of an expert
        g = jnp.where(jnp.cumsum(g > 0, axis=0) <= cap, g, 0.0)
    return g


def expert_layer(bp, m: dict, f, precision):
    """routed (the held experts' terms) + shared.  f (b, t, d)."""
    x = f.reshape(-1, f.shape[-1])
    g = gates(bp["moe"], m, x, precision)  # (rows, held)

    def add_expert(acc, e):
        w1, w2, ge = e
        return acc + ge[:, None] * gated_mlp(w1, w2, x, precision), None

    routed, _ = jax.lax.scan(add_expert, jnp.zeros_like(x),
                             (bp["moe"]["w1"], bp["moe"]["w2"], g.T))
    shared = gated_mlp(bp["shared"]["fc1"]["kernel"],
                       bp["shared"]["fc2"]["kernel"], x, precision)
    if precision == "fault_no_shared":
        shared = 0.0
    return (routed + shared).reshape(f.shape)


def layer(bp, m: dict, h, precision, attn: bool):
    u = rms_norm(h, bp["norm"]["weight"], m["norm_eps"])
    mix = (attention(bp["mixer"], m, u, precision) if attn
           else model.mamba2_mixer(bp["mixer"], m, u, products(precision)))
    h = h + mix * m["residual_multiplier"]
    f = rms_norm(h, bp["norm2"]["weight"], m["norm_eps"])
    return h + expert_layer(bp, m, f, precision) * m["residual_multiplier"]


def embed(rows, m: dict):
    return rows.astype(jnp.float32) * m["embedding_multiplier"]


def head_logits(normed, embedding, m: dict, precision):
    return mm(normed, embedding.T, precision) * m["lm_head_multiplier"]


# ------------------------------------------------------------ the model


def logits_fn(params, m: dict, ids, precision="f32", remat=False):
    """ids (b, t) -> logits (b, t, V) float32, on a float32 tree."""
    h = embed(params["embedding"][ids], m)
    body = lambda h, bp, attn: layer(bp, m, h, precision, attn)
    if remat:
        body = jax.checkpoint(body, static_argnums=(2,))
    i_m = i_a = 0
    for i in range(m["n_layer"]):
        attn = is_attn(m, i)
        stack, j = ((params["attn_blocks"], i_a) if attn
                    else (params["blocks"], i_m))
        h = body(h, jax.tree.map(lambda a: a[j], stack), attn)
        i_a, i_m = i_a + attn, i_m + (not attn)
    normed = rms_norm(h, params["norm_f"]["weight"], m["norm_eps"])
    return head_logits(normed, params["embedding"], m, precision)


def loss_sum(params, m: dict, ids, targets, precision="f32"):
    """Sum over every position of the cross-entropy of ``targets``."""
    lg = logits_fn(params, m, ids, precision, remat=True)
    lse = jax.nn.logsumexp(lg, axis=-1)
    tgt = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - tgt)


@functools.lru_cache(maxsize=None)
def _programs(m_items: tuple, dtype: str, precision: str, jit) -> dict:
    """The walk's programs, each compiled once a process."""
    m = dict(m_items)
    nb = _vocab_blocks(m)
    rows = m["vocab_size"] // nb

    def keys(key):
        return layer_keys(key, m)

    def embed_ids(k, ids):
        # a block of the table's rows at a time; an id's row is taken from
        # the block that holds it, and no block outlives its turn
        def take(h, kb_j):
            kb, j = kb_j
            local = ids - j * rows
            mine = (local >= 0) & (local < rows)
            got = embed(embedding_block(kb, m, dtype)[
                jnp.clip(local, 0, rows - 1)], m)
            return jnp.where(mine[..., None], got, h), None

        h0 = jnp.zeros(ids.shape + (m["d_model"],), jnp.float32)
        return jax.lax.scan(take, h0, (jax.random.split(k, nb),
                                       jnp.arange(nb)))[0]

    def draw_mamba(ks, j):
        return cast(draw_mamba_block(ks[j], m, dtype), jnp.float32)

    def draw_attn(ks, j):
        return cast(draw_attn_block(ks[j], m, dtype), jnp.float32)

    def mamba(h, bp):
        return layer(bp, m, h, precision, False)

    def attn(h, bp):
        return layer(bp, m, h, precision, True)

    def head(h, k, pos):
        w = draw_norm_f(m, dtype)["weight"].astype(jnp.float32)
        normed = rms_norm(h[0, pos], w, m["norm_eps"])
        blocks = jax.lax.map(
            lambda kb: head_logits(
                normed, embedding_block(kb, m, dtype).astype(jnp.float32),
                m, precision),
            jax.random.split(k, nb))
        return jnp.moveaxis(blocks, 0, 1).reshape(pos.shape[0], -1)

    return {f.__name__: jit(f) for f in (keys, embed_ids, draw_mamba,
                                         draw_attn, mamba, attn, head)}


def served_logits(key, m: dict, dtype, ids, pos, precision="f32", jit=jax.jit):
    """ids (1, t), pos (k,) -> float32 logits (k, V) at ``pos``.  The
    embedding does not live through the walk (the tied head draws its blocks
    again), and of the layers one's weights are alive at a time."""
    p = _programs(freeze(m), dtype, precision, jit)
    k_emb, k_m, k_a = p["keys"](key)
    h = p["embed_ids"](k_emb, ids)
    i_m = i_a = 0
    for i in range(m["n_layer"]):
        if is_attn(m, i):
            h = p["attn"](h, p["draw_attn"](k_a, i_a))
            i_a += 1
        else:
            h = p["mamba"](h, p["draw_mamba"](k_m, i_m))
            i_m += 1
    return p["head"](h, k_emb, pos)


# ------------------------------------------------------------ operations


def expert_layer_flops(m: dict) -> float:
    """One token through one layer's router, its share of the routed
    experts and the shared expert.  A token's ``moe_top_k`` choices fall on
    the held experts in the held share of the router's width (a seed's
    router is near uniform; the program's counter reads the share run)."""
    d = m["d_model"]
    _, n = held(m)
    f = 2 * d * m["moe_num_experts"]  # router
    f += m["moe_top_k"] * (n / m["moe_num_experts"]) * 6 * d * m["d_intermediate"]
    f += 6 * d * m["moe_shared_intermediate"]
    return f


def layer_flops(m: dict, context: float, attn: bool) -> float:
    """One layer, one token that attends ``context`` keys; model convention:
    2 operations a parameter of every matrix the token passes, the recurrent
    form of the state-space layer, attention's scores and values."""
    d = m["d_model"]
    if attn:
        nh, nkv, hd = (m["attn_num_heads"], m["attn_num_kv_heads"],
                       m["attn_head_dim"])
        f = 2 * d * (nh + 2 * nkv) * hd  # qkv
        f += 4 * context * nh * hd  # scores + values over ``context`` keys
        f += 2 * nh * hd * d  # out-projection
    else:
        s = mamba_dims(m)
        f = 2 * d * s["d_in_proj"]  # in-projection
        f += 2 * s["conv_dim"] * s["w"]  # depthwise conv
        f += 2 * (2 * s["nh"] * s["n"] * s["hp"])  # state update + readout
        f += 2 * s["di"] * d  # out-projection
    return f + expert_layer_flops(m)


def forward_flops_per_token(m: dict, context: float,
                            logit_positions: float = 0.0) -> float:
    """One forward, for a token that attends ``context`` keys.
    ``logit_positions`` is the share of positions whose logits are needed
    (a prompt's tokens need none but its last); the harness, which asks
    with two arguments, counts none, and so under-counts by the head's
    2 x 411 M operations for each sampled token."""
    return (sum(layer_flops(m, context, is_attn(m, i))
                for i in range(m["n_layer"]))
            + logit_positions * 2 * m["d_model"] * m["vocab_size"])


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Three forwards' worth, the head on every position."""
    return 3.0 * forward_flops_per_token(m, seq_len / 2, logit_positions=1.0)
