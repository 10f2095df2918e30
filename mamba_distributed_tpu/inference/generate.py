"""Recurrent generation with top-k sampling.

Functional upgrade of the reference's generate/top_k_sampling
(/root/reference/model.py:49-95, train.py:166-199): same sampling recipe
(top-k 50, softmax over the k logits, categorical draw), but the decode
loop carries the O(1) recurrent state (conv cache + SSM state per layer)
instead of re-running the full growing prefix through the model each token
— the reference never used its dep's ``inference_params`` (SURVEY.md §3.3).

Everything (prefill scan + decode scan) is one jit; token-for-token the
logits match the full-sequence forward (pinned by tests/test_model.py
decode-parity and tests/test_inference.py).

Serving contracts (mamba_distributed_tpu/serving/ reuses all of this):

* Prompt lengths are bucketed to powers of two for pure-SSM stacks
  (inference/bucketing.py) so heterogeneous prompts share jit traces —
  the padded prefill is numerically equivalent to the unpadded one
  (~1e-7 summation-order noise for off-bucket lengths; pass
  ``length_bucketing=False`` to reproduce pre-bucketing streams
  exactly).  Prompts longer than ``cfg.effective_prefill_chunk_tokens``
  instead run the serving chunk step chunk-by-chunk
  (serving/prefill.py) — the identical computation the engine performs,
  so long-prompt parity is exact by construction.
* The per-step sampling key is ``fold_in(key, i)`` — reproducible from
  (request key, tokens-generated counter) alone, which is what lets the
  serving engine's slot-pooled decode emit the same token stream as a
  solo ``generate`` call with the same key (tests/test_serving.py).
* ``eos_id`` moves EOT stopping into the decode loop: finished rows emit
  ``eos_id`` deterministically for the rest of the budget.  ``None``
  keeps the old truncate-on-host contract.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from mamba_distributed_tpu.config import ModelConfig
from mamba_distributed_tpu.inference.bucketing import (
    next_pow2_bucket,
    pad_to_bucket,
    use_chunked_prefill,
)
from mamba_distributed_tpu.models.lm import lm_prefill, lm_step

# Python-side-effect trace counters: _generate_impl / _decode_impl bump
# these exactly once per jit trace (retraces are what the bucketing
# exists to bound — pinned by
# tests/test_serving.py::test_generate_length_bucketing_traces and
# tests/test_prefill.py; the serving engine keeps its own counters in
# serving/engine.py, the chunk step's lives in serving/prefill.py).
TRACE_COUNTS = {"generate": 0, "decode": 0}


def top_k_sample(
    key: jax.Array,
    logits: jax.Array,
    k: int = 50,
    temperature: float = 1.0,
) -> jax.Array:
    """Sample from the top-k renormalized distribution.  logits (b, V) -> (b,)."""
    vals, idx = jax.lax.top_k(logits, k)
    choice = jax.random.categorical(key, vals / temperature)
    return jnp.take_along_axis(idx, choice[:, None], axis=1)[:, 0]


def vocab_pad_mask(cfg: ModelConfig) -> jax.Array:
    """(V_padded,) additive mask: 0 for real tokens, -inf for the
    vocab-padding rows (tied zero-padded embeddings give them logit 0.0,
    which would outrank real negative logits)."""
    return jnp.where(
        jnp.arange(cfg.vocab_size_padded) < cfg.vocab_size, 0.0, -jnp.inf
    )


def _decode_params(params: dict, cfg: ModelConfig) -> dict:
    """Pre-cast matmul kernels + embedding to the compute dtype.

    Decode is weight-bandwidth-bound: every token step re-read the fp32
    params only for ``linear()`` to cast them to bf16 (~1.1 GB/token at
    280M — exactly the measured 1.38 ms/token on v5e).  Casting once
    outside the decode scan halves that traffic, and the values are
    bit-identical because the per-step cast produced the same bf16
    numbers.  Conv kernels, biases, norm weights, SSM scalars and the
    MoE router (routed in fp32) stay fp32 — their math runs in fp32.

    ``cfg.serving_weight_dtype="int8"`` goes further (ops/quant.py):
    the ``linear()``-routed kernels and the embedding become symmetric
    per-channel int8 (``{"kernel": int8, "scale": f32}``, scale axis =
    the tensor-parallel axis) instead of bf16, halving resident weight
    bytes again; the matmul sites dequantize at use.  The serving
    engine and ``generate()`` both quantize HERE — one shared cast —
    so the quantized engine==generate() parity argument mirrors the
    bf16 one (toleranced: ops/quant.assert_stream_close).  mamba1's
    dt_proj kernel stays on the bf16 cast (its matmul bypasses
    ``linear`` — the dt bias folds into the scan's fp32 delta path).
    """
    cd = jnp.dtype(cfg.compute_dtype)
    if cfg.serving_weight_dtype == "int8":
        from mamba_distributed_tpu.ops.quant import quantize_serving_params

        # quantize FROM THE FP32 MASTERS (before any bf16 cast — the
        # scales keep full precision); the cast below then skips the
        # int8 kernels and their f32 scales
        params = quantize_serving_params(params)

    def cast(path, leaf):
        # denylist contract: every "kernel" leaf is a bf16-matmul weight
        # UNLESS its parent is named here because its math must stay fp32.
        # Adding a new fp32-math matmul param under a new key REQUIRES
        # extending this tuple + test_decode_params_cast_selectivity
        # (tests/test_inference.py), which pins the casted/uncasted split.
        keys = [getattr(p, "key", None) for p in path]
        if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.integer):
            return leaf  # int8 quantized kernels stay as-is
        if keys and keys[-1] == "scale":
            return leaf  # quantization scales stay f32
        if keys and keys[-1] == "embedding":
            return leaf.astype(cd)
        if (
            keys
            and keys[-1] == "kernel"
            and len(keys) >= 2
            and keys[-2] not in ("conv", "router")
        ):
            return leaf.astype(cd)
        if keys[-2:] in (["moe", "w1"], ["moe", "w2"]):
            return leaf.astype(cd)  # the routed experts' stacked kernels
        return leaf

    return jax.tree_util.tree_map_with_path(cast, params)


def _decode_scan(
    params: dict,
    cfg: ModelConfig,
    state,
    last_logits: jax.Array,
    key: jax.Array,
    max_new_tokens: int,
    top_k: int,
    temperature: float,
    eos_id: jax.Array,
) -> jax.Array:
    """The decode loop: (prefill state, last logits) -> (b, n) sampled
    tokens.  ONE definition shared by ``_generate_impl`` (one-shot
    prefill) and ``_decode_impl`` (chunked prefill), so the two paths'
    decode numerics cannot diverge."""
    b = last_logits.shape[0]
    pad_mask = vocab_pad_mask(cfg)
    has_eos = eos_id >= 0

    def decode(carry, i):
        state, logits, done = carry
        # fold_in (not split) so the serving engine can reproduce step i's
        # key from (request key, per-slot counter) without a static budget
        tok = top_k_sample(
            jax.random.fold_in(key, i), logits + pad_mask, top_k, temperature
        )
        # `done` implies has_eos (it is only ever set below), so finished
        # rows deterministically keep emitting the eos token
        tok = jnp.where(done, eos_id, tok)
        done = done | (has_eos & (tok == eos_id))
        logits, state = lm_step(params, cfg, state, tok)
        return (state, logits, done), tok

    done0 = jnp.zeros((b,), bool)
    (_, _, _), new_tokens = jax.lax.scan(
        decode, (state, last_logits, done0), jnp.arange(max_new_tokens)
    )
    return jnp.moveaxis(new_tokens, 0, 1)


def _constrain_tp(params: dict, mesh):
    """Pin the decode-cast params to their serving tensor-parallel
    layout (``mesh`` a 2-D serving_mesh with model > 1; None = no-op).
    Delegates to the ONE shared constraint the serving engine's tick/
    prefill/chunk step also apply, so a solo ``generate(mesh=)``
    partitions its math identically — the engine==generate()
    bit-parity contract at ``model > 1``."""
    if mesh is None:
        return params
    from mamba_distributed_tpu.parallel.sharding import (
        constrain_serving_params,
    )

    return constrain_serving_params(params, mesh)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "max_new_tokens", "top_k", "temperature", "mesh"),
)
def _generate_impl(
    params: dict,
    cfg: ModelConfig,
    prompt_ids: jax.Array,
    token_mask: jax.Array | None,
    key: jax.Array,
    max_new_tokens: int,
    top_k: int,
    temperature: float,
    eos_id: jax.Array,
    mesh=None,
) -> jax.Array:
    """(b, T_bucket) padded prompt -> (b, T_bucket + max_new_tokens).

    ``eos_id`` is a traced int32 scalar (-1 => no EOS stopping, the same
    sentinel the serving tick uses) so switching tokenizers never
    recompiles."""
    TRACE_COUNTS["generate"] += 1  # python side effect: runs once per trace
    b, t = prompt_ids.shape
    params = _constrain_tp(_decode_params(params, cfg), mesh)
    # parallel prefill: one full-sequence forward builds the decode state
    # (the reference re-ran the whole prefix per token instead)
    last_logits, state = lm_prefill(
        params, cfg, prompt_ids, max_len=t + max_new_tokens,
        token_mask=token_mask,
    )
    new_tokens = _decode_scan(
        params, cfg, state, last_logits, key, max_new_tokens, top_k,
        temperature, eos_id,
    )
    return jnp.concatenate([prompt_ids, new_tokens], axis=1)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "max_new_tokens", "top_k", "temperature", "mesh"),
)
def _decode_impl(
    params: dict,
    cfg: ModelConfig,
    state,
    last_logits: jax.Array,
    key: jax.Array,
    max_new_tokens: int,
    top_k: int,
    temperature: float,
    eos_id: jax.Array,
    mesh=None,
) -> jax.Array:
    """Decode from an externally built prefill state (the chunked-prefill
    path, serving/prefill.chunked_prefill) -> (b, max_new_tokens).

    One trace per (cfg, budget, sampling statics) regardless of prompt
    length — the prompt's shape never enters this function."""
    TRACE_COUNTS["decode"] += 1  # python side effect: runs once per trace
    params = _constrain_tp(_decode_params(params, cfg), mesh)
    return _decode_scan(
        params, cfg, state, last_logits, key, max_new_tokens, top_k,
        temperature, eos_id,
    )


def generate(
    params: dict,
    cfg: ModelConfig,
    prompt_ids: jax.Array,
    key: jax.Array,
    max_new_tokens: int = 32,
    top_k: int = 50,
    temperature: float = 1.0,
    eos_id: int | None = None,
    length_bucketing: bool = True,
    mesh=None,
    prefix_cache=None,
    drafter=None,
) -> jax.Array:
    """prompt_ids (b, t) int32 -> (b, t + max_new_tokens) sampled tokens.

    ``mesh`` (a 2-D ``parallel/mesh.serving_mesh``) runs the prefill +
    decode with the weights tensor-parallel over the mesh's ``model``
    axis — the SAME per-parameter constraint the serving engine
    applies, so a solo call with an engine's mesh stays bit-identical
    to the engine's streams at ``serving_model_shards > 1``.  None
    (default) is the unsharded path, unchanged.

    ``eos_id=None``: EOT stopping is a host-side concern (the full budget
    is generated; truncate at the tokenizer's EOT afterwards, as the
    caller wishes).  With ``eos_id`` set, rows that sample it keep
    emitting ``eos_id`` deterministically for the rest of the budget, so
    the output is directly truncatable and token-for-token reproducible
    by the serving engine.

    ``length_bucketing`` pads the prompt to a power-of-two bucket (pure-
    SSM stacks only) so any workload of heterogeneous prompt lengths
    compiles O(log max_len) traces instead of one per distinct length.
    Prompts longer than ``cfg.prefill_chunk_tokens`` (when > 0) instead
    prefill chunk-by-chunk through the serving chunk step
    (serving/prefill.py) — ONE compiled chunk shape + one decode trace
    for any prompt length, and the exact computation the serving engine
    runs, which is what keeps engine-vs-generate() token parity exact
    for long prompts too.

    ``prefix_cache`` (a serving/prefix_cache.PrefixCache; pure-SSM,
    batch-1) reuses carry snapshots: an exact-prompt full hit skips the
    prefill outright (one-shot AND chunked layouts), a chunked partial
    hit resumes at the first uncached chunk, and chunked prefills store
    their boundaries back.  Sharing an engine's cache (same params!)
    makes warm engine==generate() parity directly testable — and warm
    streams are bit-identical to cold ones regardless, because a
    snapshot is the identical computation's literal output.  Hybrid
    configs ignore the cache here (their entries pin a serving
    engine's KV page pool).

    ``cfg.spec_tokens > 0`` routes greedy (``top_k=1``) batch-1 calls
    through the SPECULATIVE path (serving/spec_decode.spec_generate):
    the identical draft -> verify -> accept/rollback loop the serving
    engine's spec tick runs, so engine==generate() parity holds by
    construction there too — and greedy speculative streams are token-
    identical to non-speculative greedy ones (speculation is lossless
    under argmax).  ``drafter`` overrides the config-built drafter (a
    serving/spec_decode.Drafter — required for ``spec_drafter=
    "model"``, whose companion params aren't derivable from cfg); it
    only moves the acceptance rate, never the tokens.  Non-greedy or
    batched calls fall through to the normal path unchanged.
    """
    b, t = prompt_ids.shape
    hybrid = bool(cfg.attn_layer_idx)
    chunk = cfg.effective_prefill_chunk_tokens
    if (mesh is not None and dict(mesh.shape).get("model", 1) <= 1
            and dict(mesh.shape).get("stage", 1) <= 1):
        # a data-only serving mesh shards slots, not weights — nothing
        # for generate() to constrain; dropping it keeps the TP-off jit
        # signatures (and pinned trace counts) identical to pre-TP.
        # A model OR stage axis > 1 partitions the weights (TP columns
        # / pipeline layer groups), so those meshes must be kept.
        mesh = None
    if cfg.spec_tokens > 0 and top_k == 1 and b == 1 and length_bucketing:
        # deferred import: serving imports this module at package-load
        # time, so the reverse edge must stay out of import time
        from mamba_distributed_tpu.serving.spec_decode import spec_generate

        return spec_generate(
            params, cfg, prompt_ids, max_new_tokens=max_new_tokens,
            eos_id=eos_id, mesh=mesh, prefix_cache=prefix_cache,
            drafter=drafter,
        )
    if length_bucketing and (
        (chunk > 0) if hybrid else use_chunked_prefill(t, chunk)
    ):
        # deferred import: serving imports this module at package-load
        # time, so the reverse edge must stay out of import time.
        # HYBRID prompts of ANY length go through the chunk step — it is
        # the one prefill that both masks pad keys (pads never reach the
        # paged KV) and is the exact computation the serving engine runs,
        # so hybrid engine<->generate() parity is by construction too.
        from mamba_distributed_tpu.serving.prefill import chunked_prefill

        last_logits, state = chunked_prefill(
            params, cfg, prompt_ids,
            max_len=(t + max_new_tokens) if hybrid else 0, mesh=mesh,
            prefix_cache=None if hybrid else prefix_cache,
        )
        new_tokens = _decode_impl(
            params, cfg, state, last_logits, key, max_new_tokens, top_k,
            temperature, jnp.int32(-1 if eos_id is None else eos_id),
            mesh=mesh,
        )
        return jnp.concatenate([prompt_ids, new_tokens], axis=1)
    if (prefix_cache is not None and not hybrid and b == 1
            and length_bucketing):
        # one-shot full hit: decode straight off the cached snapshot
        # (an engine's one-shot admission stores these — same pow2
        # layout, same key — so an exact prompt repeat skips lm_prefill
        # here too).  The one-shot path cannot STORE (its prefill state
        # never leaves the fused _generate_impl jit), but misses still
        # go through lookup() so hit/miss/promotion accounting matches
        # the engine's on a shared cache.
        hit = prefix_cache.lookup(np.asarray(prompt_ids[0]), None)
        if hit is not None:
            entry = hit[0]
            new_tokens = _decode_impl(
                params, cfg, {"blocks": entry.state["blocks"]},
                entry.logits, key, max_new_tokens, top_k, temperature,
                jnp.int32(-1 if eos_id is None else eos_id), mesh=mesh,
            )
            return jnp.concatenate([prompt_ids, new_tokens], axis=1)
    if length_bucketing and not cfg.attn_layer_idx:
        padded, mask = pad_to_bucket(prompt_ids, next_pow2_bucket(t))
    else:
        padded, mask = prompt_ids, None
    out = _generate_impl(
        params, cfg, padded, mask, key, max_new_tokens, top_k, temperature,
        jnp.int32(-1 if eos_id is None else eos_id), mesh=mesh,
    )
    if padded.shape[1] == t:
        return out
    # splice the unpadded prompt back onto the generated suffix
    return jnp.concatenate([prompt_ids, out[:, padded.shape[1]:]], axis=1)
