"""Symmetric per-channel int8 quantization for the serving path.

Serving decode is weight-bandwidth-bound (docs/SERVING.md): every tick
re-reads the full weights, so halving weight bytes is a direct per-chip
throughput AND capacity multiplier — the same trade the SNIPPETS [2]/[3]
serving stacks make by sharding ``torch.int8`` attention/MLP weights
over their tp/fsdp axes.  Quantization and tensor parallelism compose
here the same way: the quantization (scale) axis of every parameter is
chosen to be its tensor-parallel axis (parallel/sharding._TP_RULES), so
a sharded weight's scales live on the same shard as its channels and no
cross-shard rescale is ever needed:

  * column-parallel kernels (in_proj, wqkv, fc1, lm_head) scale per
    OUTPUT column -> dequant folds into the matmul output:
    ``y = (x @ q) * scale``;
  * row-parallel kernels (out_proj, x_proj, fc2) scale per INPUT row
    -> dequant folds into the activation: ``y = (x * scale) @ q``;
  * the embedding (V, d) scales per VOCAB row — one scale family serves
    both the lookup (``q[ids] * scale[ids]``) and the tied LM head
    (``(x @ q.T) * scale``), and the vocab axis is exactly what
    ``serving_param_specs`` column-parallelizes.

Both forms are exact per-channel dequantization (a diagonal scale
commutes through the contraction), and neither materializes a full-
precision weight copy — XLA fuses the int8->compute cast and the scale
multiply into the dot.

A quantized leaf is a dict ``{"kernel": int8, "scale": f32}`` where the
scale keeps the kernel's rank with every non-channel axis sized 1
(``keepdims``) — ``models/common.linear`` reads the orientation off the
shape (trailing 1 => row scales) and ``parallel/sharding``'s serving
specs shard the scale's channel axis with the kernel's.  The embedding
leaf becomes the same dict shape-for-shape, handled by ``models/lm``'s
embed/tied-head helpers.

What quantizes: exactly the matmul kernels the decode cast
(inference/generate._decode_params) sends to the compute dtype and that
route through ``models/common.linear`` — plus the embedding.  What does
NOT: conv kernels, the MoE router AND expert stacks (w1/w2 run through
their own einsums, not ``linear`` — an fp8/MoE follow-on, ROADMAP),
mamba1's dt_proj (its bias folds into the scan's fp32 delta path and
its matmul bypasses ``linear``), biases, norm scales, and the SSM
scalars — all of whose math stays fp32/bf16 as before.

``assert_stream_close`` is the quantized parity contract's shared
checker (tests/test_quant_serving.py): bf16 serving pins streams
bit-exact; int8 serving pins logit closeness + greedy-token agreement
over the stream, with the PR-2 divergence sentinels counting any
disagreement.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# int8 symmetric range: scales map the per-channel absmax onto +-127
Q_MAX = 127.0
# scale floor: an all-zero channel must not divide by zero (its q rows
# are all zero anyway, so any finite scale round-trips it exactly)
SCALE_EPS = 1e-12

# (path-suffix pattern, channel-axis-from-end) for quantizable kernels
# that ``linear()`` consumes.  -1 = column-parallel (scale per output
# column), -2 = row-parallel (scale per input row) — mirroring
# parallel/sharding._TP_RULES so scales shard with their weights.
_QUANT_RULES: tuple[tuple[tuple[str, ...], int], ...] = (
    (("mixer", "in_proj", "kernel"), -1),
    (("mixer", "out_proj", "kernel"), -2),
    (("mixer", "x_proj", "kernel"), -2),
    (("mixer", "wqkv", "kernel"), -1),
    (("mlp", "fc1", "kernel"), -1),
    (("mlp", "fc2", "kernel"), -2),
    (("shared", "fc1", "kernel"), -1),  # an expert layer's shared expert
    (("shared", "fc2", "kernel"), -2),
    (("lm_head", "kernel"), -1),
)


def quant_axis(names: list[str]) -> int | None:
    """Channel (scale) axis-from-end for a param path, or None when the
    leaf does not quantize.  ``names`` is the tree path as strings."""
    for pattern, ax in _QUANT_RULES:
        k = len(pattern)
        if tuple(names[-k:]) == pattern:
            return ax
    return None


def quantize_channels(w: jax.Array, axis: int) -> dict:
    """Symmetric per-channel int8: scale = absmax/127 along every axis
    EXCEPT ``axis`` (and any leading layer-stack axes are preserved —
    each layer's channels quantize independently because the reduction
    never touches them... it reduces only the one contraction axis for
    2-D-per-layer kernels).

    Concretely: for a kernel of rank r with channel axis ``axis``
    (negative, from the end), the reduction runs over the OTHER of the
    two trailing axes; leading (layer/expert) axes are kept.  Returns
    ``{"kernel": int8, "scale": f32}`` with the scale keeping the
    kernel's rank (reduced axis sized 1) so consumers can read the
    orientation off the shape.
    """
    r = w.ndim
    ax = axis % r
    # the contraction axis is the *other* trailing axis
    red = r - 1 if ax == r - 2 else r - 2
    absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=red, keepdims=True)
    scale = jnp.maximum(absmax / Q_MAX, SCALE_EPS)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -Q_MAX, Q_MAX)
    return {"kernel": q.astype(jnp.int8), "scale": scale}


def quantize_embedding(emb: jax.Array) -> dict:
    """(V, d) embedding -> per-vocab-row int8: scale (V, 1).  Serves the
    lookup and the tied head with one scale family (module docstring) —
    the same symmetric rule, channel axis 0."""
    return quantize_channels(emb, 0)


def quantize_serving_params(params: dict) -> dict:
    """Quantize a (fp32 master) param tree for serving: every
    ``linear()``-routed kernel named by ``_QUANT_RULES`` becomes
    ``{"kernel": int8, "scale": f32}`` IN PLACE of its dict (bias and
    any other siblings ride along untouched), and the embedding array
    becomes the same dict form.  Everything else — conv, router,
    dt_proj, biases, norms, SSM scalars, the routed experts ``moe/w1``
    and ``moe/w2`` (einsum and ragged_dot operands, not ``linear()``'s;
    int8 experts are ROADMAP D7) — passes through for the decode cast to
    handle as before.  Called from
    ``inference/generate._decode_params`` (the ONE shared decode cast)
    when ``cfg.serving_weight_dtype == "int8"``."""

    def walk(tree, names):
        if not isinstance(tree, dict):
            return tree
        out = {}
        for k, v in tree.items():
            if is_quantized(v):
                # idempotent: re-quantizing an already-quantized leaf
                # against its own int8 values would destroy the scales
                out[k] = v
                continue
            if k == "embedding" and not isinstance(v, dict):
                out[k] = quantize_embedding(v)
                continue
            if isinstance(v, dict) and "kernel" in v and not isinstance(
                    v["kernel"], dict):
                ax = quant_axis(list(names) + [k, "kernel"])
                if ax is not None:
                    q = quantize_channels(v["kernel"], ax)
                    out[k] = {**{kk: vv for kk, vv in v.items()
                                 if kk != "kernel"}, **q}
                    continue
            out[k] = walk(v, names + (k,))
        return out

    return walk(params, ())


def apply_dtype_overrides(cfg, weight_dtype: str | None = None,
                          kv_dtype: str | None = None):
    """``dataclasses.replace`` the serving dtype knobs when given — the
    ONE place a caller's weight / KV-page dtype choice would land
    (no caller since the old bench CLIs went: ROADMAP D9), so a
    future knob (the fp8 follow-on) threads through one function."""
    import dataclasses

    kw = {}
    if weight_dtype:
        kw["serving_weight_dtype"] = weight_dtype
    if kv_dtype:
        kw["kv_page_dtype"] = kv_dtype
    return dataclasses.replace(cfg, **kw) if kw else cfg


def dequantize(leaf) -> jax.Array:
    """Materialize a quantized leaf back to f32 (tests / round-trip
    error bounds; the serving hot paths never call this — they fold the
    scale into the matmul instead)."""
    if isinstance(leaf, dict) and "scale" in leaf:
        return leaf["kernel"].astype(jnp.float32) * leaf["scale"]
    return leaf


def is_quantized(leaf) -> bool:
    return isinstance(leaf, dict) and "scale" in leaf and "kernel" in leaf


def param_bytes(params) -> int:
    """Resident bytes of a (possibly quantized) param tree — the
    ``weight_bytes`` gauge serving telemetry stamps when quant is on."""
    return sum(int(x.nbytes) for x in jax.tree.leaves(params))


# --------------------------------------------------------------------- KV
# Per-(page, kv-head) int8 page math shared by the lax fallback and the
# host-side scale planner (models/attention.py); the Pallas kernels
# mirror it in-register (ops/pallas/attention_kernels.py).


def kv_requant(q_old: jax.Array, ratio: jax.Array) -> jax.Array:
    """Re-express old int8 page rows under a new scale: ``round(q_old *
    old_scale/new_scale)``.  ``ratio`` broadcasts over the (page, hd)
    block; scales only grow within a page's life (the update rule keeps
    ``new >= old`` whenever the page has prior content), so the ratio is
    <= 1 and the result stays in range — the clip is a garbage-row
    guard, not a correctness crutch."""
    return jnp.clip(jnp.round(q_old.astype(jnp.float32) * ratio),
                    -Q_MAX, Q_MAX)


def kv_quantize(x: jax.Array, scale: jax.Array) -> jax.Array:
    """Quantize fresh K/V rows under the page's (new) scale."""
    return jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -Q_MAX, Q_MAX)


# ----------------------------------------------------------------- parity


def assert_stream_close(
    got_tokens,
    want_tokens,
    got_logits=None,
    want_logits=None,
    *,
    rtol: float = 2e-2,
    atol: float = 5e-2,
    min_token_agreement: float = 1.0,
    sentinel=None,
    metrics=None,
    label: str = "",
) -> int:
    """The quantized-parity checker: toleranced engine==generate().

    ``got_tokens``/``want_tokens`` are int token streams of equal
    intent (engine slot stream vs solo ``generate()`` suffix).  The
    comparison is prefix-based: once one token differs the tails are
    conditioned on different contexts and comparing them further is
    meaningless, so agreement = matched-prefix length over the compared
    length.  ``min_token_agreement=1.0`` (default) demands exact
    greedy-token agreement — what the int8 path delivers in practice
    because the engine and ``generate()`` run the IDENTICAL quantized
    math — while still reporting any disagreement through the PR-2
    divergence-sentinel machinery instead of an opaque array mismatch:

      * ``sentinel`` (an obs.DivergenceSentinel) gets one
        ``quant_token_disagreement`` flight-recorder event;
      * ``metrics`` (a utils.metrics.ServingMetrics) gets its
        greedy-disagreement counter bumped.

    ``got_logits``/``want_logits`` (optional, aligned to the streams)
    are compared with ``np.allclose(rtol, atol)`` over the MATCHED
    prefix only.  Returns the number of disagreeing tail tokens (0 on
    full agreement).  Bit-exact bf16 streams pass trivially.
    """
    got = np.asarray(got_tokens).reshape(-1)
    want = np.asarray(want_tokens).reshape(-1)
    if got.shape != want.shape:
        raise AssertionError(
            f"stream lengths differ{f' ({label})' if label else ''}: "
            f"{got.shape} vs {want.shape}"
        )
    n = len(got)
    neq = np.nonzero(got != want)[0]
    matched = int(neq[0]) if len(neq) else n
    disagreed = n - matched
    if disagreed:
        if sentinel is not None:
            sentinel.record_event(
                "quant_token_disagreement", label=label,
                first_divergence=matched, compared=n,
                got=int(got[matched]), want=int(want[matched]),
            )
        if metrics is not None:
            metrics.record_greedy_disagreement(disagreed)
    agreement = matched / n if n else 1.0
    if agreement < min_token_agreement:
        raise AssertionError(
            f"token streams diverge at {matched}/{n}"
            f"{f' ({label})' if label else ''}: "
            f"got[{matched}]={got[matched]} want[{matched}]={want[matched]} "
            f"(agreement {agreement:.3f} < {min_token_agreement})"
        )
    if got_logits is not None and want_logits is not None and matched:
        gl = np.asarray(got_logits, np.float32)[:matched]
        wl = np.asarray(want_logits, np.float32)[:matched]
        if not np.allclose(gl, wl, rtol=rtol, atol=atol):
            worst = float(np.max(np.abs(gl - wl)))
            raise AssertionError(
                f"logits diverge beyond tolerance over the matched "
                f"prefix{f' ({label})' if label else ''}: max abs diff "
                f"{worst:.4g} (rtol={rtol}, atol={atol})"
            )
    return disagreed
