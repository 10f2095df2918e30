"""Plain float32 reference of the language models the benchmark runs.

Written from the published equations (Mamba-2: Dao & Gu 2024, "Transformers
are SSMs", the SSD layer in its direct quadratic form; attention: grouped-query
causal softmax attention with rotate-half RoPE; prenorm RMSNorm residual
blocks; a tied LM head; mean cross-entropy).  It imports nothing of the
program (``mamba_distributed_tpu``): it reads the benchmark's own weights
(``reference/init.py``) by their names and a configuration as a plain dict
(``benchmark/configs/<name>.json``, key ``model``).  ``reference/mamba2.py``
is the module the kinds find it through.

No kernels, no cache, no chunked scan, no paging: every position is computed
from the whole sequence.  ``mm`` is the only place a matrix product is formed,
so the lower-precision *control* of the benchmark's ``correct`` (fp8
operands) is this same code with ``precision`` switched.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------ sizes


def dims(m: dict) -> dict:
    """Derived sizes of a configuration's ``model`` dict."""
    d = m["d_model"]
    di = m["expand"] * d
    hp = m["headdim"]
    nh = di // hp
    g = m["ngroups"]
    n = m["d_state"]
    out = dict(d=d, di=di, hp=hp, nh=nh, g=g, n=n, w=m["d_conv"],
               d_in_proj=2 * di + 2 * g * n + nh, conv_dim=di + 2 * g * n,
               vocab=m["vocab_size"], n_layer=m["n_layer"],
               attn_idx=tuple(m.get("attn_layer_idx", ())))
    if out["attn_idx"]:
        out.update(anh=m["attn_num_heads"], akv=m["attn_num_kv_heads"],
                   ahd=m["attn_head_dim"], theta=m["rope_theta"])
    return out


# ------------------------------------------------------------ products


def _quant_fp8(x):
    """Round to float8 e4m3 with one scale per tensor (the usual recipe:
    amax mapped to the format's largest finite value, 448)."""
    s = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(x, w, precision: str):
    """``x @ w`` with float32 accumulation.

    precision: "f32" — float32 operands, six-pass products (HIGHEST);
    "fp8" — operands rounded to float8 e4m3: the control, never the
    reference.
    """
    if precision == "fp8":
        x, w = _quant_fp8(x), _quant_fp8(w)
    elif precision != "f32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


# ------------------------------------------------------------ layers


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def causal_conv(x, kernel, bias):
    """Depthwise causal convolution.  x (b, t, c); kernel (c, w): tap j
    multiplies the input ``w - 1 - j`` steps in the past."""
    w = kernel.shape[1]
    t = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (w - 1, 0), (0, 0)))
    y = sum(xp[:, j:j + t] * kernel[:, j] for j in range(w))
    return y + bias


def ssd(x, dt, A, B, C, D, q_block: int = 256):
    """The state-space layer in its direct form.

    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T ;  y_t = C_t h_t + D x_t,
    unrolled: y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} dt_r A) dt_s x_s.

    x (b, t, h, p); dt (b, t, h) > 0; A (h,) < 0; B, C (b, t, g, n); D (h,).
    Query positions are taken ``q_block`` at a time so that the (t, t)
    decay matrix never exists whole.
    """
    b, t, h, p = x.shape
    g = B.shape[2]
    rep = h // g
    cum = jnp.cumsum(dt * A, axis=1)  # (b, t, h), decreasing
    xdt = x * dt[..., None]
    tq = -(-t // q_block) * q_block
    pad = lambda a: jnp.pad(a, ((0, 0), (0, tq - t)) + ((0, 0),) * (a.ndim - 2))
    Cq = pad(C).reshape(b, tq // q_block, q_block, g, -1).swapaxes(0, 1)
    # pad rows repeat the last row, so that no exponent is ever positive
    cq = jnp.pad(cum, ((0, 0), (0, tq - t), (0, 0)), mode="edge")
    cq = cq.reshape(b, tq // q_block, q_block, h).swapaxes(0, 1)
    iq = jnp.arange(tq).reshape(tq // q_block, q_block)
    s_idx = jnp.arange(t)

    def block(args):
        Cb, cb, ib = args  # (b, q, g, n), (b, q, h), (q,)
        G = jnp.einsum("bqgn,bsgn->bgqs", Cb, B, precision=HIGHEST)
        diff = cb[:, :, None, :] - cum[:, None, :, :]  # (b, q, s, h)
        mask = (s_idx[None, :] <= ib[:, None])[None, :, :, None]
        L = jnp.exp(jnp.where(mask, diff, -jnp.inf))  # (b, q, s, h)
        M = L.reshape(b, q_block, t, g, rep) * G.transpose(0, 2, 3, 1)[..., None]
        return jnp.einsum("bqsh,bshp->bqhp", M.reshape(b, q_block, t, h),
                          xdt, precision=HIGHEST)

    y = jax.lax.map(block, (Cq, cq, iq))  # (nq, b, q, h, p)
    y = y.swapaxes(0, 1).reshape(b, tq, h, p)[:, :t]
    return y + x * D[:, None]


def mamba2_mixer(p, m, u, precision):
    s = dims(m)
    b, t, _ = u.shape
    zxbcdt = mm(u, p["in_proj"]["kernel"], precision)
    z = zxbcdt[..., :s["di"]]
    xBC = zxbcdt[..., s["di"]:s["di"] + s["conv_dim"]]
    dt = zxbcdt[..., s["di"] + s["conv_dim"]:]
    xBC = jax.nn.silu(causal_conv(xBC, p["conv"]["kernel"], p["conv"]["bias"]))
    x = xBC[..., :s["di"]].reshape(b, t, s["nh"], s["hp"])
    B = xBC[..., s["di"]:s["di"] + s["g"] * s["n"]].reshape(b, t, s["g"], s["n"])
    C = xBC[..., s["di"] + s["g"] * s["n"]:].reshape(b, t, s["g"], s["n"])
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = ssd(x, dt, -jnp.exp(p["A_log"]), B, C, p["D"])
    y = y.reshape(b, t, s["di"])
    y = rms_norm(y * jax.nn.silu(z), p["norm"]["weight"], m["norm_eps"])
    return mm(y, p["out_proj"]["kernel"], precision)


def rope(x, theta):
    """Rotate-half RoPE over the whole head.  x (b, t, h, hd)."""
    t, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv  # (t, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention_mixer(p, m, u, precision, q_block: int = 512):
    s = dims(m)
    b, t, _ = u.shape
    nh, nkv, hd = s["anh"], s["akv"], s["ahd"]
    qkv = mm(u, p["wqkv"]["kernel"], precision)
    q = rope(qkv[..., :nh * hd].reshape(b, t, nh, hd), s["theta"])
    k = rope(qkv[..., nh * hd:(nh + nkv) * hd].reshape(b, t, nkv, hd), s["theta"])
    v = qkv[..., (nh + nkv) * hd:].reshape(b, t, nkv, hd)
    rep = nh // nkv
    tq = -(-t // q_block) * q_block
    qp = jnp.pad(q, ((0, 0), (0, tq - t), (0, 0), (0, 0)))
    qb = qp.reshape(b, tq // q_block, q_block, nkv, rep, hd).swapaxes(0, 1)
    iq = jnp.arange(tq).reshape(tq // q_block, q_block)
    s_idx = jnp.arange(t)

    def block(args):
        qq, ib = args  # (b, q, nkv, rep, hd)
        sc = jnp.einsum("bqgrh,bkgh->bgrqk", qq, k, precision=HIGHEST)
        sc = sc / math.sqrt(hd)
        sc = jnp.where(s_idx[None, :] <= ib[:, None], sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bgrqk,bkgh->bqgrh", w, v, precision=HIGHEST)

    o = jax.lax.map(block, (qb, iq)).swapaxes(0, 1)
    o = o.reshape(b, tq, nh * hd)[:, :t]
    return mm(o, p["out_proj"]["kernel"], precision)


# ------------------------------------------------------------ the model


def _segments(m: dict):
    """The layer order as runs: ("mamba", lo, hi) over the stacked mamba
    blocks, ("attn", j) for the j-th attention block."""
    attn = set(m.get("attn_layer_idx", ()))
    out, mi, ai, run = [], 0, 0, 0
    for i in range(m["n_layer"]):
        if i in attn:
            if run:
                out.append(("mamba", mi - run, mi))
                run = 0
            out.append(("attn", ai))
            ai += 1
        else:
            mi += 1
            run += 1
    if run:
        out.append(("mamba", mi - run, mi))
    return out


def block(mixer, bp, m, h, precision):
    """One prenorm residual block: ``h + mixer(norm(h))``."""
    u = rms_norm(h, bp["norm"]["weight"], m["norm_eps"])
    return h + mixer(bp["mixer"], m, u, precision)


def hidden_states(params, m, ids, precision="f32", remat=False):
    """ids (b, t) -> the residual stream after the last block, (b, t, d)."""
    h = params["embedding"][ids]

    def mamba_block(h, bp):
        return block(mamba2_mixer, bp, m, h, precision), None

    def attn_block(h, bp):
        return block(attention_mixer, bp, m, h, precision)

    if remat:
        mamba_block = jax.checkpoint(mamba_block)
        attn_block = jax.checkpoint(attn_block)
    for seg in _segments(m):
        if seg[0] == "mamba":
            stack = jax.tree.map(lambda a: a[seg[1]:seg[2]], params["blocks"])
            h, _ = jax.lax.scan(mamba_block, h, stack)
        else:
            h = attn_block(
                h, jax.tree.map(lambda a: a[seg[1]], params["attn_blocks"]))
    return h


def logits_fn(params, m, ids, precision="f32"):
    """ids (b, t) -> logits (b, t, V) float32, tied head."""
    h = hidden_states(params, m, ids, precision)
    normed = rms_norm(h, params["norm_f"]["weight"], m["norm_eps"])
    return mm(normed, params["embedding"].T, precision)


def loss_sum(params, m, ids, targets, precision="f32"):
    """Sum over every position of the cross-entropy of ``targets``."""
    h = hidden_states(params, m, ids, precision, remat=True)
    normed = rms_norm(h, params["norm_f"]["weight"], m["norm_eps"])
    lg = mm(normed, params["embedding"].T, precision)
    lse = jax.nn.logsumexp(lg, axis=-1)
    tgt = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - tgt)
