"""Chunked-prefill subsystem: plan, compiled chunk step, shared driver.

A long prompt's prefill is just a resumable scan — Mamba's decode state
is O(1), and the mixers accept ``initial_conv_state``/``initial_ssm_state``
carries — so instead of one pow2-bucketed forward per prompt (a new jit
trace per length class, up to 2x padding waste, and a tick-stalling
monolith in the serving engine), prompts longer than
``cfg.prefill_chunk_tokens`` run as a sequence of fixed-shape chunk
calls:

  * ``plan_chunks`` pads the prompt (LEFT, like the pow2 buckets) to the
    next multiple of the chunk size and splits it into equal chunks —
    the pad lives entirely inside chunk 0, under the usual ``token_mask``;
  * ``prefill_chunk`` is the one compiled step: ids + mask + carried
    state -> (last logits, new state), via ``models/lm.lm_prefill_chunk``.
    ONE trace per (model config, chunk size, batch) no matter how long
    prompts get — ``TRACE_COUNTS["chunk"]`` pins it
    (tests/test_prefill.py);
  * ``chunked_prefill`` drives a whole prompt through the chunk step —
    the solo ``generate()`` path.  The serving engine drives the same
    step itself, chunk by chunk between decode ticks, parking the carry
    in the request's slot (state_cache.stash_prefill) when its per-tick
    token budget runs out.

The chunk carry is also the DISAGGREGATION currency: on a prefill-tier
replica (docs/SERVING.md "Disaggregated tiers") the completed prompt's
carry + last logits — the exact outputs the last chunk step returns —
become the O(1) migration artifact a decode replica restores, so
splitting the phases across replicas costs one host round-trip of the
same snapshot prefix caching and preemption already move.

Parity: the engine and ``generate()`` run the SAME jitted chunk step
over the SAME padded chunk layout with params cast by the SAME jitted
cast, so their prefill states — and therefore token streams — are
bit-identical by construction (the pow2-bucket playbook, extended).
Chunked vs ONE-SHOT prefill over the same layout is exact for the conv
caches (the carry is the literal trailing inputs) and ~1e-6 for the SSM
states (the inter-chunk fp32 state recurrence re-associates; see
lm_prefill_chunk's docstring) — pinned at tolerance by
tests/test_prefill.py.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from mamba_distributed_tpu.config import ModelConfig
from mamba_distributed_tpu.inference.bucketing import (
    chunk_aligned_bucket,
    use_chunked_prefill,
)
from mamba_distributed_tpu.inference.generate import _decode_params
from mamba_distributed_tpu.models.lm import init_lm_state, lm_prefill_chunk

# Python-side-effect trace counter: one bump per jit trace of the chunk
# step.  The whole point of the fixed chunk shape is that this stays at
# one per (cfg, chunk, batch) for any prompt-length mix — pinned by
# tests/test_prefill.py::test_chunk_step_traces_once.
TRACE_COUNTS = {"chunk": 0}


@functools.partial(jax.jit, static_argnames=("cfg",))
def _cast_decode_tree(params: dict, cfg: ModelConfig) -> dict:
    return _decode_params(params, cfg)


@functools.partial(jax.jit, static_argnames=("dtypes",))
def _cast_leaves(leaves: list, dtypes: tuple) -> list:
    return [x.astype(d) for x, d in zip(leaves, dtypes)]


def cast_decode_params(params: dict, cfg: ModelConfig) -> dict:
    """Decode-layout param cast (inference/generate._decode_params), jitted
    once at module level so the serving engine and ``generate()``'s
    chunked path share one compilation AND produce bit-identical cast
    values — an input to the chunk-step parity argument above.

    A leaf that already has its decode dtype comes out as the buffer it
    went in as: only the leaves the cast changes go through the compiled
    program, so a tree handed over in its serving dtype (bfloat16 weights
    that pass half the chip) is held once, not twice.  (The int8 cast
    changes the tree's structure and every matmul leaf with it, and runs
    whole.)"""
    if cfg.serving_weight_dtype != "bf16":
        return _cast_decode_tree(params, cfg=cfg)
    leaves, treedef = jax.tree.flatten(params)
    want = [
        x.dtype for x in jax.tree.leaves(
            jax.eval_shape(functools.partial(_decode_params, cfg=cfg), params)
        )
    ]
    moved = [i for i, (x, d) in enumerate(zip(leaves, want)) if x.dtype != d]
    out = [jnp.asarray(x) for x in leaves]  # a device array is itself
    for i, x in zip(moved, _cast_leaves([leaves[i] for i in moved],
                                        tuple(want[i] for i in moved))):
        out[i] = x
    return jax.tree.unflatten(treedef, out)


@dataclasses.dataclass(frozen=True)
class ChunkPlan:
    """How one prompt splits into prefill chunks (host-side, static)."""

    prompt_len: int
    chunk: int  # tokens per chunk (cfg.effective_prefill_chunk_tokens)
    bucket: int  # padded length = n_chunks * chunk
    n_chunks: int

    @property
    def pad(self) -> int:
        """Left-pad tokens (all inside chunk 0)."""
        return self.bucket - self.prompt_len

    def real_tokens(self, i: int) -> int:
        """Non-pad prompt tokens in chunk ``i`` — what advances the
        hybrid KV length mirror, and the chunk's share of USEFUL work
        in the goodput accounting (``chunk - real`` lanes are padding
        waste; utils/metrics.record_tick)."""
        return self.chunk - (self.pad if i == 0 else 0)


def plan_chunks(prompt_len: int, chunk_tokens: int,
                force: bool = False) -> ChunkPlan | None:
    """The chunk planner.  None => the prompt takes the one-shot pow2
    path (too short to chunk, or chunking disabled).  ``force`` plans
    even prompts that fit one chunk (>= 1 chunk) — the HYBRID path,
    where every prompt runs through the chunk step because it is the
    one prefill that both masks pad keys (pads are never written to KV
    pages) and writes straight into the paged pool."""
    if not use_chunked_prefill(prompt_len, chunk_tokens):
        if not (force and chunk_tokens > 0):
            return None
    bucket = chunk_aligned_bucket(prompt_len, chunk_tokens)
    return ChunkPlan(
        prompt_len=prompt_len,
        chunk=chunk_tokens,
        bucket=bucket,
        n_chunks=bucket // chunk_tokens,
    )


def chunk_inputs(
    prompt_ids: np.ndarray, plan: ChunkPlan, i: int
) -> tuple[jax.Array, jax.Array]:
    """ids + mask for chunk ``i`` of the left-padded layout.

    prompt_ids (b, t) -> ids (b, chunk) int32, mask (b, chunk) f32 {0,1}.
    Pad positions (chunk 0's first ``plan.pad`` columns) hold token id 0
    and mask 0 — the same contract as ``pad_to_bucket``.
    """
    if not 0 <= i < plan.n_chunks:
        raise ValueError(f"chunk {i} out of range [0, {plan.n_chunks})")
    ids = np.asarray(prompt_ids, np.int32)
    if ids.ndim == 1:
        ids = ids[None, :]
    b, t = ids.shape
    if t != plan.prompt_len:
        raise ValueError(f"prompt length {t} != plan.prompt_len {plan.prompt_len}")
    lo, hi = i * plan.chunk, (i + 1) * plan.chunk  # in padded coordinates
    pad = plan.pad
    out = np.zeros((b, plan.chunk), np.int32)
    mask = np.zeros((b, plan.chunk), np.float32)
    # real tokens occupy padded positions [pad, bucket)
    src_lo, src_hi = max(lo, pad) - pad, hi - pad
    dst_lo = max(lo, pad) - lo
    out[:, dst_lo:] = ids[:, src_lo:src_hi]
    mask[:, dst_lo:] = 1.0
    return jnp.asarray(out), jnp.asarray(mask)


@functools.partial(jax.jit, static_argnames=("cfg", "mesh", "return_load"),
                   donate_argnums=(3,))
def prefill_chunk(
    params: dict, ids: jax.Array, mask: jax.Array, state, cfg: ModelConfig,
    mesh=None, adapter_ids: jax.Array | None = None,
    return_load: bool = False,
):
    """The compiled chunk step: (ids, mask, carry) -> (last logits, carry').

    ``return_load`` (static; the engine sets it for a model with expert
    layers) adds a third result: the chunk's expert load, (held + 1,) int32,
    of its REAL tokens over the layers (models/lm._moe_mlp).

    ``params`` must already be decode-cast (``cast_decode_params``) —
    both drivers pass the same cast output, which is what makes their
    chunk computations bit-identical.  ``state`` is donated: for hybrid
    stacks it carries the (large) paged KV pool through every chunk, and
    since the layer loop carries the pool whole and the kernel addresses
    a layer of it by index (models/lm._hybrid_layers), the donation lets
    XLA write pages in place: no slice, stacked copy or write-back of
    the pool is made per chunk.

    ``mesh`` (static; a 2-D ``serving_mesh`` with ``model > 1``, else
    None) re-asserts the tensor-parallel weight layout inside the jit —
    the same constraint the engine's tick applies — so the engine's
    chunk dispatches and ``generate(mesh=)``'s run ONE partitioning and
    the chunk-step parity argument survives weight sharding.  None (the
    default, and everything below ``serving_model_shards=2``) keeps the
    signature — and the trace counts tests pin — byte-identical to the
    pre-TP step.
    """
    TRACE_COUNTS["chunk"] += 1
    if mesh is not None:
        from mamba_distributed_tpu.parallel.sharding import (
            constrain_serving_params,
        )

        params = constrain_serving_params(params, mesh)
    if adapter_ids is not None:
        # multi-tenant LoRA (serving/adapters.py): bind the batch rows'
        # adapter ids into the attached factor pools so this chunk's
        # projections add the request's segmented delta — the SAME
        # per-row math the tick applies, which is what keeps a LoRA
        # stream's prefill and decode on one adapter identity
        from mamba_distributed_tpu.serving.adapters import (
            bind_adapter_ids,
        )

        params = bind_adapter_ids(params, adapter_ids)
    return lm_prefill_chunk(params, cfg, ids, state, token_mask=mask,
                            return_load=return_load)


def chunked_prefill(
    params: dict, cfg: ModelConfig, prompt_ids,
    plan: ChunkPlan | None = None, max_len: int = 0, mesh=None,
    prefix_cache=None,
):
    """Drive a whole prompt through the chunk step (the solo-`generate()`
    driver; the serving engine paces the same loop itself, against its
    per-tick budget).

    ``params`` are the fp32 master params — cast here via the shared
    jitted cast.  For HYBRID stacks ``max_len`` (prompt + decode budget)
    sizes the private paged KV cache; its page count is pow2-bucketed so
    the downstream decode trace count stays O(log pages) across prompt/
    budget mixes (page-width differences never perturb the token stream
    — masked attention is bit-stable across page-bucket widths, see
    models/attention.py).  ``mesh`` (a 2-D serving_mesh with model > 1,
    else None) threads the tensor-parallel weight constraint into every
    chunk call — pass the serving engine's mesh to reproduce its chunk
    computation bit-for-bit.  Returns (last_logits (b, V) fp32, state),
    the ``lm_prefill`` contract, ready for the decode loop.

    ``prefix_cache`` (a serving/prefix_cache.PrefixCache; batch-1
    PURE-SSM prompts only — hybrid entries pin a serving engine's page
    pool and are unusable here) reuses and refreshes carry snapshots:
    a full hit returns the cached (logits, state) with zero chunk
    calls, a partial hit seeds the deepest cached boundary carry (a
    COPY — the chunk step donates its state argument, and a donated
    cache entry would be destroyed), and completed chunks store their
    boundaries back.  Cached carries are the literal outputs of this
    exact layout's chunk steps, so warm results are bit-identical to
    cold ones — and to a cache-enabled serving engine's, which shares
    both the layout and the key scheme (tests/test_prefix_cache.py).
    """
    prompt = np.asarray(prompt_ids, np.int32)
    if prompt.ndim == 1:
        prompt = prompt[None, :]
    b, t = prompt.shape
    hybrid = bool(cfg.attn_layer_idx)
    if plan is None:
        plan = plan_chunks(t, cfg.effective_prefill_chunk_tokens,
                           force=hybrid)
    if plan is None:
        raise ValueError(
            f"prompt length {t} does not take the chunked path "
            f"(prefill_chunk_tokens={cfg.effective_prefill_chunk_tokens}); use "
            f"lm_prefill via the pow2 bucket instead"
        )
    dparams = cast_decode_params(params, cfg=cfg)
    if hybrid:
        if max_len < t:
            raise ValueError(
                f"hybrid chunked prefill needs KV capacity for the whole "
                f"request: max_len={max_len} < prompt length {t}"
            )
        from mamba_distributed_tpu.inference.bucketing import (
            next_pow2_bucket,
        )
        from mamba_distributed_tpu.models.attention import (
            attention_page_count,
        )

        pages = next_pow2_bucket(
            attention_page_count(cfg, max_len), min_bucket=1
        )
        state = init_lm_state(cfg, batch=b,
                              max_len=pages * cfg.kv_page_tokens)
    else:
        state = init_lm_state(cfg, batch=b)
    use_cache = prefix_cache is not None and not hybrid and b == 1
    start = 0
    if use_cache:
        hit = prefix_cache.lookup(prompt[0], plan)
        if hit is not None:
            entry, start = hit
            if start == plan.n_chunks:
                # full hit: the snapshot IS this layout's prefill output
                return entry.logits, {"blocks": entry.state["blocks"]}
            # seed a COPY: prefill_chunk donates its state argument, and
            # donating the cached arrays would destroy the entry
            state = {"blocks": jax.tree.map(jnp.copy, entry.state["blocks"])}
    logits = None
    for i in range(start, plan.n_chunks):
        ids, mask = chunk_inputs(prompt, plan, i)
        logits, state = prefill_chunk(dparams, ids, mask, state, cfg=cfg,
                                      mesh=mesh)
        if use_cache:
            # the output carry feeds the NEXT chunk's donation — store a
            # copy (tiny: the O(1) conv+SSM carry) ... except the last,
            # which nothing donates again
            keep = (state["blocks"] if i == plan.n_chunks - 1
                    else jax.tree.map(jnp.copy, state["blocks"]))
            prefix_cache.maybe_store_boundary(
                prompt[0], plan, i, {"blocks": keep})
            if i == plan.n_chunks - 1:
                prefix_cache.maybe_store_full(
                    prompt[0], {"blocks": keep}, logits,
                    chunk=plan.chunk, chunks=plan.n_chunks)
    return logits, state
