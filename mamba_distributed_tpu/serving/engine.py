"""Continuous-batching serving engine over the pooled recurrent-state cache.

One compiled decode tick advances EVERY occupied slot by ``tokens_per_tick``
tokens; finished and empty slots are masked, and new requests are admitted
into freed slots between ticks — bucketed prefill (inference/bucketing.py)
plus ``state_cache.insert`` write a request's state into its slot without
retracing anything.  Decode is weight-bandwidth-bound, so filling more
slots costs (nearly) nothing per tick: aggregate tokens/sec scales with
occupancy (docs/SERVING.md; the benchmark's serving cells measure it on
the chip: benchmark/README.md).

Long prompts (``t > cfg.prefill_chunk_tokens``) prefill in CHUNKS
(serving/prefill.py) interleaved with decode ticks: each ``step()``
spends at most ``cfg.prefill_tokens_per_tick`` tokens of chunk work
(oldest request first) before running the tick, and a half-prefilled
request keeps its slot with its scan carry parked in the pool
(``state_cache.stash_prefill``; the tick masks such slots from sampling
and from state writes) until the next budget grant resumes it.  Short
prompts keep the PR-1 behavior: a one-shot pow2-bucketed prefill at
admission, not counted against the chunk budget (they are at most
~chunk-sized by construction).  This bounds both the TTFT of short
requests and the ITL of running slots while a long prompt streams in —
the head-of-line blocking tests/test_prefill.py pins by tick counts.

Speculative decoding (``cfg.spec_tokens = K > 0``; serving/
spec_decode.py, docs/SERVING.md "Speculative decoding") swaps the
decode tick for a K-token draft-verify tick: one ``lm_verify_chunk``
launch scores a drafter's K guesses for every live slot and commits
the longest correct prefix — up to K+2 tokens per full weight read,
greedy-only and token-identical to the non-speculative stream.

Parity contract: a request's token stream is bit-identical to a solo
``generate(params, cfg, prompt[None], key, ...)`` call with the same key
whenever ``request.top_k == engine.max_top_k`` (the static top-k width),
regardless of what else shares the batch.  The pieces that make this
hold, pinned by tests/test_serving.py and tests/test_prefill.py:

* both pad the same prompt to the same bucket — pow2 one-shot for short
  prompts, the chunk-aligned layout driven through the SAME jitted
  chunk step for long ones (neither is an engine knob: both live on
  ModelConfig / the bucketing module, so the two callers can never
  disagree);
* the step-i sampling key is ``fold_in(request_key, i)``, reproducible
  from the per-slot counter alone — and a vmapped per-row
  ``categorical`` draws the same bits as generate's batch-1 call;
* ``lm_step`` is row-independent, so co-batched strangers can't
  perturb a slot's logits.

Requests with ``top_k < max_top_k`` are served via masking (positions
beyond the slot's k get -inf) — a valid top-k draw, but from a different
noise stream than a solo ``generate(top_k=k)`` call would use.
"""

from __future__ import annotations

import dataclasses
import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding, PartitionSpec as P

from mamba_distributed_tpu.config import ModelConfig
from mamba_distributed_tpu.inference.bucketing import next_pow2_bucket, pad_to_bucket
from mamba_distributed_tpu.obs import (
    NULL_TRACER,
    StreamingHistogram,
    annotated,
    scopes,
)
from mamba_distributed_tpu.inference.generate import vocab_pad_mask
from mamba_distributed_tpu.models.attention import attention_page_count
from mamba_distributed_tpu.models.lm import (
    init_lm_blocks_state,
    lm_prefill,
    lm_step,
)
from mamba_distributed_tpu.serving import adapters as adapters_mod
from mamba_distributed_tpu.serving import prefix_cache as prefix_cache_mod
from mamba_distributed_tpu.serving import spec_decode
from mamba_distributed_tpu.serving import state_cache
from mamba_distributed_tpu.serving.sessions import SessionStoreError
from mamba_distributed_tpu.serving.prefix_cache import PrefixCache
from mamba_distributed_tpu.serving.prefill import (
    cast_decode_params,
    chunk_inputs,
    plan_chunks,
    prefill_chunk,
)
from mamba_distributed_tpu.serving.scheduler import (
    FCFSScheduler,
    GenerationRequest,
    GenerationResult,
    RequestStatus,
    TenantQuotaExceeded,
    TokenEvent,
    _Tracked,
    check_tenant_quota,
)
from mamba_distributed_tpu.utils.metrics import ServingMetrics
from mamba_distributed_tpu.utils.platform import (
    describe_devices,
    key_compile_cache_by_scopes,
)

# Python-side-effect trace counters (one bump per jit trace) — the
# bucketing exists to bound these; tests/test_serving.py pins them (the
# chunk step's counter lives in serving/prefill.py, pinned by
# tests/test_prefill.py).
TRACE_COUNTS = {"prefill": 0, "tick": 0}

# The decode tick's lane ladder (docs/SERVING.md "Occupancy-adaptive
# ticks").  A data shard's rungs start at RUNG_FLOOR_LANES, double, and end
# at its share of the capacity.  Under 8 lanes nothing is saved: the
# projections pad their rows to 8 sublanes and a sub-step's weight read does
# not shrink, while every rung costs one more program in each set-up.  A
# rung is left for a narrower one only after RUNG_HYSTERESIS_TICKS
# consecutive ticks that would have fitted it (it is taken for a wider one
# at once).
RUNG_FLOOR_LANES = 8
RUNG_HYSTERESIS_TICKS = 4


def tick_rungs(capacity: int, num_shards: int = 1) -> tuple[int, ...]:
    """The widths a decode tick may launch at, narrowest first: the floor a
    shard, doubled while under the shard's slots, then the capacity.  It
    depends on nothing but what is given: 96 -> 8, 16, 32, 64, 96; 16 -> 8,
    16; an engine of at most the floor's slots a shard has one rung."""
    per = capacity // num_shards
    lanes, b = [], RUNG_FLOOR_LANES
    while b < per:
        lanes.append(b)
        b *= 2
    return tuple(n * num_shards for n in lanes) + (capacity,)


@functools.partial(jax.jit, static_argnames=("cfg", "mesh"))
def _prefill(params: dict, ids: jax.Array, mask: jax.Array, cfg: ModelConfig,
             mesh=None, adapter_ids=None):
    """Bucketed batch-1 prompt prefill -> (last_logits (1, V), state).

    ``mesh`` (static; only passed when the serving mesh has a model
    axis > 1) re-asserts the tensor-parallel weight layout so this
    prefill partitions exactly like ``generate(mesh=)``'s — an input to
    the engine==generate() parity argument at ``model > 1``.
    ``adapter_ids`` (LoRA engines only; (1,) int32) binds the request's
    factor-pool row so the prefill computes the same segmented delta
    the ticks will (serving/adapters.py)."""
    TRACE_COUNTS["prefill"] += 1
    if mesh is not None:
        from mamba_distributed_tpu.parallel.sharding import (
            constrain_serving_params,
        )

        params = constrain_serving_params(params, mesh)
    if adapter_ids is not None:
        params = adapters_mod.bind_adapter_ids(params, adapter_ids)
    return lm_prefill(params, cfg, ids, token_mask=mask)


@functools.partial(
    jax.jit, static_argnames=("cfg", "k_max", "steps", "mesh", "n_micro"),
    donate_argnums=(1,),
)
def _tick(params: dict, pool: dict, tbl=None, lengths=None, lanes=None, *,
          cfg: ModelConfig, k_max: int, steps: int, mesh=None,
          n_micro=None):
    """Advance every slot ``steps`` tokens.  Returns (pool', tokens
    (steps, S), emitted (steps, S), done (steps, S), load) — ``emitted[j,
    s]`` marks a real token (slot live at sub-step j), ``done[j, s]`` the
    slot's finish state after it; the rest is masked garbage.  The host
    consumes ``done`` rather than re-deriving the finish rule, so there
    is exactly one copy of it (here).  ``load`` (held + 1,) int32 is the
    expert layers' (models/lm._moe_mlp) over the launch's sub-steps and
    layers: a held expert's rows from the live lanes, and last the held
    experts reached; None for a dense model, whose program it leaves as
    it was.

    ``lanes`` — ``(idx, keep)``, (W,) int32 and (W,) bool, both traced —
    makes the launch a NARROW rung of the engine's ladder: the rows of
    slots ``idx`` are gathered into W lanes, the sub-steps run at lane
    width (``tbl``/``lengths`` are then the lanes' rows, and the three
    outputs are (steps, W)), and the advanced lanes are written back into
    the donated pool row by row, pad lanes (``~keep``) dropped
    (``state_cache.gather_rows`` / ``scatter_rows``).  Gather, sub-steps
    and write-back are one program, so one dispatch a tick at any rung;
    ``lanes=None`` is the ladder's last rung, the whole pool, with no
    gather and no write-back.  Per-row mathematics is the same at every
    rung.

    HYBRID stacks additionally take the host-owned paged-KV metadata:
    ``tbl`` (S, B) int32 — page-table rows sliced to the tick's page
    BUCKET B (pow2 of the largest active slot's allocation, so attention
    reads scale with what is actually resident, and one trace per bucket
    covers every occupancy/length mix) — and ``lengths`` (S,) int32.
    The per-sub-step KV writes of non-live slots are routed to the trash
    page via ``lm_step``'s write_mask, so a dead slot can never touch a
    page that was recycled to someone else; the host re-derives the
    lengths advance from ``emitted`` (bit-equal: both count live
    sub-steps), so nothing metadata-shaped needs fetching.

    The conv + SSM carry of a slot parked mid-chunked-prefill survives
    the tick through ``lm_step``'s ``state_mask`` (``~prefilling``): the
    update returns those rows unchanged, and every layer loop of
    ``lm_step`` (the pure-SSM scan; the hybrid's group scan and unrolled
    loop) carries the stacked pool, so the donated pool is one buffer
    updated in place from entry to exit — no select and no copy over the
    (L, S, ...) leaves.  The hybrid's KV page pool rides the same carry:
    a slot's one-token row is scattered into it and the decode kernel
    reads a layer of it by index, so nothing the size of the pool, or of
    a layer's pages, is sliced, stacked or copied around the kernels.
    Only the (S, V) logits are selected here.

    Mirrors generate()'s decode loop exactly: sample from the carried
    logits with key fold_in(key, step), then lm_step.  Slots that hit
    their eos keep feeding it forward (same as generate's eos_id path);
    slots that are empty or budget-done still compute — that waste is
    the price of a single static-shape trace, and it is reclaimed by
    admitting new requests into those slots between ticks.

    ``n_micro`` (static; only ever set when ``mesh`` has a ``stage``
    axis > 1 and the stack is pure-SSM) engages the explicit GPipe
    schedule inside ``lm_step``: the slot lanes split into ``n_micro``
    microbatches that flow through the stage-resident layer groups
    (parallel/pipeline.pipelined_decode_layers) — bitwise identical to
    the sequential layer scan, only the placement of work changes.
    ``n_micro=None`` at ``stage > 1`` still runs correctly: GSPMD
    executes the stage-sharded layer scan without the explicit
    microbatch clock.
    """
    TRACE_COUNTS["tick"] += 1
    pad_mask = vocab_pad_mask(cfg)
    col = jnp.arange(k_max)[None, :]
    hybrid = tbl is not None
    if mesh is not None:
        # the shard_slots path (static ``mesh``, a serving_mesh): pin
        # the slot/page state — and the host-owned per-slot tick inputs
        # — to their data-axis layout so the batched lm_step partitions
        # its batch axis instead of decaying to one device, whatever
        # the between-ticks insert/evict propagation concluded.  With a
        # model axis > 1 the WEIGHTS get the same treatment on their
        # tensor-parallel axis (serving_param_shardings): GSPMD then
        # runs every slot's lm_step as d_inner/head-sharded matmuls
        # with compiler-inserted all-reduces — 2-D parallelism, slots
        # over data x weights over model.
        from mamba_distributed_tpu.parallel.sharding import (
            constrain_serving_params,
            slot_axis_sharding,
            slot_pool_shardings,
        )

        if (dict(mesh.shape).get("model", 1) > 1
                or dict(mesh.shape).get("stage", 1) > 1):
            params = constrain_serving_params(params, mesh)
        pool = jax.lax.with_sharding_constraint(
            pool, slot_pool_shardings(pool, mesh)
        )
        if hybrid:
            tbl = jax.lax.with_sharding_constraint(
                tbl, slot_axis_sharding(mesh)
            )
            lengths = jax.lax.with_sharding_constraint(
                lengths, slot_axis_sharding(mesh)
            )
    whole = None
    if lanes is not None:
        # the pool proper stays behind as ``whole``; the sub-steps see the
        # lanes (the hybrid's shared page pool has no slot axis and rides
        # along as it is)
        whole = pool
        pool = _with_slot_rows(pool, state_cache.gather_rows(
            _slot_rows(pool), *lanes, mesh=mesh))
    # multi-tenant LoRA (serving/adapters.py): bind each slot's factor-
    # pool row from the pool meta into the attached pools — a no-op
    # tree walk on LoRA-less params (no "lora" subtrees), and the ids
    # are constant across the tick's sub-steps (admission happens
    # between ticks), so one bind serves the whole scan.
    params = adapters_mod.bind_adapter_ids(
        params, pool["meta"]["adapter_id"]
    )

    def one(carry, _):
        pool, lengths = carry
        meta = pool["meta"]
        # a slot mid-chunked-prefill is resident but NOT live: it emits
        # nothing, and its parked scan carry must survive the tick
        live = meta["active"] & ~meta["done"] & ~meta["prefilling"]
        has_eos = meta["eos_id"] >= 0
        with jax.named_scope(scopes.SAMPLE):
            keys = jax.vmap(jax.random.fold_in)(meta["key"], meta["step"])
            vals, idx = jax.lax.top_k(pool["logits"] + pad_mask, k_max)
            vals = jnp.where(col < meta["top_k"][:, None], vals, -jnp.inf)
            # per-row categorical: same bits as generate's batch-1 draw
            choice = jax.vmap(
                lambda k, v, t: jax.random.categorical(k, v / t)
            )(keys, vals, meta["temperature"])
            tok = jnp.take_along_axis(idx, choice[:, None], axis=1)[:, 0]
            tok = jnp.where(meta["done"] & has_eos, meta["eos_id"], tok)
        # empty/done slots may compute garbage freely (masked, overwritten
        # by the next insert), but a prefilling slot's rows hold a REAL
        # carry: lm_step's state_mask keeps them inside the update itself,
        # so nothing pool-sized is selected or copied here.  Only the
        # conv+SSM "blocks" subtree has a per-slot axis; the attention
        # page pool is protected by write_mask instead.
        advance = ~meta["prefilling"]
        if hybrid:
            state_in = {**pool["state"], "attn_meta": (tbl, lengths)}
            logits, state, load = lm_step(
                params, cfg, state_in, tok, write_mask=live,
                state_mask=advance, return_load=True)
            lengths = state["attn_meta"][1]
            state = {k: v for k, v in state.items() if k != "attn_meta"}
        else:
            logits, state, load = lm_step(
                params, cfg, pool["state"], tok, write_mask=live,
                pipeline=((mesh, n_micro) if n_micro else None),
                state_mask=advance, return_load=True,
            )
        with jax.named_scope(scopes.POOL_SELECT):
            logits = jnp.where(advance[:, None], logits, pool["logits"])
        step = meta["step"] + live.astype(jnp.int32)
        done = meta["done"] | (
            live & ((has_eos & (tok == meta["eos_id"])) | (step >= meta["max_new"]))
        )
        new_pool = {
            "state": state,
            "logits": logits,
            "meta": {**meta, "step": step, "done": done},
        }
        return (new_pool, lengths), (tok, live, done, load)

    # the sub-step scan carries the whole pool (the slots' recurrent state;
    # hybrid: the KV pages too), so what it moves around its body is filed
    # as a layer scan's own
    with jax.named_scope(scopes.ATTN_LAYERS if hybrid else scopes.LAYERS):
        (pool, _), (tokens, emitted, done, load) = jax.lax.scan(
            one, (pool, lengths), None, length=steps
        )
    if whole is not None:
        pool = _with_slot_rows(pool, state_cache.scatter_rows(
            _slot_rows(whole), _slot_rows(pool), *lanes, mesh=mesh))
    if load is not None:
        load = jnp.sum(load, axis=0)
    return pool, tokens, emitted, done, load


def _slot_rows(pool: dict) -> dict:
    """A pool's per-slot subtrees, as ``state_cache``'s gather and
    write-back see them (``attn_blocks``, the shared page pool, has no slot
    axis and rides the launch's own donation)."""
    return {"blocks": pool["state"]["blocks"], "logits": pool["logits"],
            "meta": pool["meta"]}


def _with_slot_rows(pool: dict, rows: dict) -> dict:
    """``pool`` with its per-slot subtrees replaced by ``rows`` (the
    inverse of ``_slot_rows``; whatever else the state holds stays)."""
    return {"state": {**pool["state"], "blocks": rows["blocks"]},
            "logits": rows["logits"], "meta": rows["meta"]}


class ServingEngine:
    """Continuous-batching host loop: FCFS admission -> compiled ticks.

    Args:
      params: trained fp32 params (cast once to the decode layout here).
      cfg: ModelConfig.  Hybrid stacks (``attn_layer_idx`` non-empty)
        serve through the paged attention KV pool: admission reserves
        ceil((prompt + max_new) / kv_page_tokens) pages up front (a
        request waits in the queue while the pool is short), every
        hybrid prompt prefills through the chunk step (which writes
        straight into its slot's pages), and eviction recycles the
        pages.  Requests must fit ``cfg.kv_slot_tokens``.
      capacity: slot count S — the max concurrent requests.
      max_top_k: static top-k width of the compiled sampler; per-request
        ``top_k`` may be anything in [1, max_top_k] (see parity note in
        the module docstring).
      tokens_per_tick: decode sub-steps fused into one compiled tick.
        Larger amortizes dispatch; smaller admits waiting requests
        sooner (admission only happens between ticks).
      prefill_tokens_per_tick: chunk-prefill token budget spent between
        consecutive ticks (oldest in-flight prefill first; at least one
        chunk per step so progress is guaranteed).  None (default) takes
        ``cfg.prefill_tokens_per_tick``; 0 => unbounded.  Short-prompt
        one-shot prefills are NOT budgeted — each is at most ~one chunk
        of work, the PR-1 admission behavior.
      retain_results: keep every finished request's GenerationResult in
        ``self.results`` (what ``run()`` reads).  A long-lived streaming
        server consuming TokenEvents should pass False — retention
        grows host memory without bound — and the final event's
        ``done``/``finish_reason`` carries the completion signal.
      metrics: a ServingMetrics, or None to create one.  Give it a
        ``jsonl_path`` to stream per-tick and per-request records.
      tracer: an obs.SpanTracer for host-side phase spans
        (``serving_admit`` / ``serving_prefill`` /
        ``serving_prefill_chunk`` / ``serving_tick``); default
        NULL_TRACER (off).  Per-request spans carry the request's
        ``trace`` id and tick spans/records the live trace-id set, so
        ``scripts/trace_export.py`` can flow-link one request's journey
        across streams.  Strictly host-side: enabling it adds zero
        device syncs and zero jit traces (pinned by tests/test_obs.py).
      slo: an obs.SLOMonitor fed every finished request's latency
        record (rolling-window p95 targets -> breach events); None
        (default) off.  The router shares ONE monitor across replicas
        so the window is fabric-wide.
      compile_watchdog: an obs.CompileWatchdog (already installed on
        jax.monitoring) drained once per tick — window deltas stamp
        ``compiles``/``compile_ms`` on the tick record, lifetime
        totals feed summary()["compile"] and GET /metrics.  None
        (default) off: records stay byte-stable.
      tick_regression: an obs.TickRegressionDetector fed every tick's
        wall ms (EWMA baseline; transition-only ``tick_regression``
        events when ticks run a factor slower than steady state).
        None (default) off.
      mesh: a ``parallel/mesh.serving_mesh`` — the sharded path (2-D
        ``(data, model)``, or 3-D ``(data, stage, model)`` when the
        pipeline axis is on).  Slot/page state and the tick's batch
        axis partition over the mesh's DATA axis; the weights
        partition over its MODEL axis (tensor parallel: Mamba d_inner
        channels, attention heads, embedding/head vocab —
        parallel/sharding.serving_param_specs; ``model=1`` replicates
        them, the exact pre-TP layout); the scan-over-layers parameter
        stacks AND the per-layer slot-state stacks partition their
        leading LAYER axis over the STAGE axis (GPipe residency: each
        stage holds only its own layers' weights, conv/SSM carries
        and KV page pools).  Pure-SSM decode ticks at ``stage > 1``
        additionally run the explicit microbatched clock
        (parallel/pipeline.pipelined_decode_layers) when the live
        width tiles over the stages — bitwise identical either way.
        One engine's pool and weights span every device in the mesh;
        ``capacity`` must divide over the data shards, d_inner/heads/
        vocab over the model shards, and every stacked layer family
        over the stage shards (checked here, loudly).  None (default)
        builds a mesh from ``cfg.serving_data_shards`` x
        ``cfg.serving_stage_shards`` x ``cfg.serving_model_shards``
        when any knob is > 1, else everything stays single-device.
        Host bookkeeping follows the device layout: a slot resident
        in data-shard d draws KV pages only from shard d's contiguous
        page range (state_cache.PagePool); the model and stage axes
        never touch page accounting — pages tile over data only.
      prefix_cache: a serving/prefix_cache.PrefixCache, or None to
        build one from ``cfg.prefix_cache_entries`` (> 0 enables; the
        default 0 keeps the cache off).  Admission matches the longest
        cached chunk-aligned prefix of each prompt and seeds the slot
        from the snapshot — a FULL hit inserts the cached state+logits
        outright (zero prefill compute, near-zero TTFT), a partial hit
        resumes chunking at the first uncached chunk.  Warm streams
        stay bit-identical to cold ones because a snapshot is the
        literal output of the identical chunk computation.  Hybrid
        entries pin KV pages in THIS engine's pool (copy-on-write
        sharing across slots, refcounted) — hybrid caches are engine-
        private; pure-SSM caches may be shared with
        ``generate(prefix_cache=)`` under the same params.

      migrate_hook: the disaggregated prefill/decode handoff
        (serving/router.py installs it on PREFILL-role replicas'
        engines).  Called as ``hook(tracked, package)`` for every slot
        that just turned decodable with zero tokens emitted — i.e. at
        prefill-complete, whether the prefill was chunked, one-shot,
        or a full prefix-cache hit.  ``package()`` serializes the
        migration artifact (the O(1) conv/SSM carry + last logits,
        plus hybrid KV page contents); a True return means the router
        re-placed the request on a decode replica (this engine frees
        the slot and its pages), False means no decode capacity — the
        slot decodes HERE (mixed-mode fallback, offered exactly once
        via ``no_migrate`` so a declined request never stalls).  The
        receiving engine admits the artifact via ``submit_migrated``
        and ``state_cache.restore`` — the resumed stream is bit-exact
        (the preempt/resume contract, tests/test_disagg.py).

      adapters: a ``serving/adapters.AdapterRegistry`` of named LoRA
        adapters (read only when ``cfg.lora_max_adapters > 0``; None
        builds an empty registry from the engine's own params —
        register before submitting).  The engine keeps its own
        bounded device ``AdapterCache`` of factor slots over the
        registry: admission ``acquire``s the request's adapter slot
        like it reserves KV pages (waits when every slot is pinned —
        no mid-flight miss), refcounts pin it while the stream is
        resident, and the per-slot ids ride the pool meta so slots
        running DIFFERENT adapters share one compiled launch
        (docs/SERVING.md "Multi-tenant LoRA").  Share one registry
        across a router's replicas so a migration target re-pins the
        factors from its own cache.  Streams under adapter ``a``
        match solo ``generate()`` on ``adapters.merge(params, a)``
        via ``ops/quant.assert_stream_close`` (the segmented delta
        re-associates float sums; tests/test_tenant_lora.py).
        Int8 weights + LoRA is a ROADMAP residual — rejected here.

      drafter: a ``serving/spec_decode.Drafter`` for speculative
        decoding (only read when ``cfg.spec_tokens > 0``).  None builds
        the config's drafter (``spec_drafter="ngram"``; ``"model"``
        REQUIRES an explicit ``ModelDrafter(draft_params, draft_cfg)``
        — the companion's params aren't derivable from cfg).  Draft
        quality moves the acceptance rate, never the tokens (greedy
        speculation is lossless), so any drafter is parity-safe.
        Drafter streams are keyed by ENGINE-LOCAL request ids — give
        each engine/replica its own instance rather than sharing one
        across a router fabric.

    Priority + preemption: requests carry a ``priority`` (higher wins;
    default ``cfg.serving_default_priority``).  When the queue's best
    request outranks a resident DECODING slot and no slot is free, the
    engine preempts the lowest-priority victim — its carry + logits
    swap to host RAM (``state_cache.restore`` puts them back with the
    token counter intact, so the resumed stream continues bit-exactly),
    its KV page refs ride along (no page churn, no re-prefill).  With
    every request at one priority the scheduler is the FCFS queue it
    always was.

    Prefill buckets are the module defaults of inference/bucketing.py —
    deliberately not a knob, so the engine and a solo ``generate()``
    call can never pad the same prompt differently (the parity
    contract depends on identical padding).
    """

    def __init__(
        self,
        params: dict,
        cfg: ModelConfig,
        capacity: int = 8,
        max_top_k: int = 50,
        tokens_per_tick: int = 8,
        prefill_tokens_per_tick: int | None = None,
        retain_results: bool = True,
        metrics: ServingMetrics | None = None,
        tracer=NULL_TRACER,
        slo=None,
        mesh=None,
        prefix_cache: PrefixCache | None = None,
        migrate_hook=None,
        drafter: spec_decode.Drafter | None = None,
        adapters: adapters_mod.AdapterRegistry | None = None,
        session_store=None,
        compile_watchdog=None,
        tick_regression=None,
    ):
        if not 1 <= max_top_k <= cfg.vocab_size_padded:
            raise ValueError(
                f"max_top_k={max_top_k} must be in [1, {cfg.vocab_size_padded}]"
            )
        if tokens_per_tick < 1:
            raise ValueError("tokens_per_tick must be >= 1")
        if prefill_tokens_per_tick is None:
            prefill_tokens_per_tick = cfg.prefill_tokens_per_tick
        if prefill_tokens_per_tick < 0:
            raise ValueError("prefill_tokens_per_tick must be >= 0 "
                             "(0 => unbounded)")
        if mesh is None and (cfg.serving_data_shards > 1
                             or cfg.serving_model_shards > 1
                             or cfg.serving_stage_shards > 1):
            from mamba_distributed_tpu.parallel.mesh import serving_mesh

            mesh = serving_mesh(cfg.serving_data_shards,
                                model_shards=cfg.serving_model_shards,
                                stage_shards=cfg.serving_stage_shards)
        self.mesh = mesh
        key_compile_cache_by_scopes()  # before the first program compiles
        # stderr: the bench scripts keep stdout for their one JSON line
        print(f"serving engine: {describe_devices(mesh)}", file=sys.stderr,
              flush=True)
        self.num_shards = 1 if mesh is None else int(mesh.shape["data"])
        self.model_shards = (
            1 if mesh is None else int(dict(mesh.shape).get("model", 1))
        )
        self.stage_shards = (
            1 if mesh is None else int(dict(mesh.shape).get("stage", 1))
        )
        if capacity % self.num_shards:
            raise ValueError(
                f"capacity={capacity} must divide over "
                f"serving_data_shards={self.num_shards} (each data shard "
                f"holds capacity/shards slot rows)"
            )
        if self.model_shards > 1:
            # clear rejection at CONSTRUCTION (d_inner/heads/vocab must
            # tile over the model axis), not a GSPMD error mid-flight
            from mamba_distributed_tpu.parallel.sharding import (
                validate_serving_model_shards,
            )

            validate_serving_model_shards(cfg, self.model_shards)
        if self.stage_shards > 1:
            # same construction-time loudness for the pipeline axis:
            # every stacked layer family must tile over the stages
            from mamba_distributed_tpu.parallel.sharding import (
                validate_serving_stage_shards,
            )

            validate_serving_stage_shards(cfg, self.stage_shards)
        self.cfg = cfg
        self.capacity = capacity
        self.max_top_k = max_top_k
        self.tokens_per_tick = tokens_per_tick
        self.prefill_tokens_per_tick = prefill_tokens_per_tick
        self.retain_results = retain_results
        self.pool = state_cache.init_pool(  # validates cfg
            cfg, capacity, self.num_shards
        )
        self._params = cast_decode_params(params, cfg=cfg)
        if mesh is not None:
            from mamba_distributed_tpu.parallel.sharding import (
                serving_param_shardings,
                slot_pool_shardings,
            )

            # weights tensor-parallel over the model axis (replicated
            # when model=1 — serving_param_specs degenerates to P()),
            # slot/page state partitioned over the data axis — the
            # layout every subsequent insert/evict/tick inherits (and
            # the tick re-asserts via its constraints)
            self._params = jax.device_put(
                self._params, serving_param_shardings(self._params, mesh)
            )
            self.pool = jax.device_put(
                self.pool, slot_pool_shardings(self.pool, mesh)
            )
        # the mesh the chunk step / one-shot prefill need for weight
        # constraints — None when neither the model nor the stage axis
        # partitions the weights, so the sharding-off jit signatures
        # (and trace counts) are byte-identical to the pre-TP engine
        self._tp_mesh = (
            mesh if (self.model_shards > 1 or self.stage_shards > 1)
            else None
        )
        self.scheduler = FCFSScheduler(
            default_priority=cfg.serving_default_priority
        )
        self.metrics = metrics or ServingMetrics(capacity)
        self.tracer = tracer
        self.slo = slo
        # --- live telemetry plane (obs/watchdog.py + obs/slo.py;
        # docs/OBSERVABILITY.md "Live telemetry plane"): an attached
        # CompileWatchdog is drained once per tick — its window deltas
        # become the record's `compiles`/`compile_ms` stamps and its
        # lifetime totals summary()["compile"] / the /metrics counters.
        # An attached TickRegressionDetector is fed every tick's wall
        # ms (EWMA baseline -> transition-only `tick_regression`
        # events).  Both None (default) keep records byte-stable.
        self.compile_watchdog = compile_watchdog
        if compile_watchdog is not None:
            self.metrics.configure_compile()
        self.tick_regression = tick_regression
        # goodput: analytic FLOPs rates (utils/flops.py, the "model"
        # convention — parameter matmuls + recurrent state math, no
        # device counters, no syncs) so every serving_tick record can
        # carry a host-computed serving_mfu.  Decode rates are per
        # sampled token; chunk-prefill rates per real prompt token at
        # the chunk's sequence length.
        from mamba_distributed_tpu.utils.flops import (
            flops_per_token,
            peak_flops_per_chip,
        )

        # with chunking disabled (one-shot only) price prefill at the
        # DEFAULT chunk width rather than seq_len=1: the length only
        # moves the O(t) attention terms, and charging a hybrid's
        # one-shot prefill at decode-length rates would systematically
        # understate serving_mfu in exactly that config
        prefill_seq = cfg.effective_prefill_chunk_tokens or 256
        # the rates are counts; the peak is a fact about a TPU — off
        # one there is none, and serving_mfu stays None
        dev = jax.devices()[0] if mesh is None else mesh.devices.flat[0]
        self.metrics.configure_goodput(
            flops_per_decode_token=flops_per_token(
                cfg, 1, training=False, convention="model"),
            flops_per_prefill_token=flops_per_token(
                cfg, prefill_seq, training=False, convention="model"),
            peak_flops=(
                peak_flops_per_chip(dev) * self.num_shards
                * self.model_shards * self.stage_shards
                if dev.platform == "tpu" else None
            ),
        )
        if self.stage_shards > 1:
            self.metrics.configure_pipeline(self.stage_shards)
        self._free: list[int] = list(range(capacity))
        self._slots: dict[int, _Tracked] = {}
        # slots holding a partial chunked prefill, in admission order;
        # the per-tick budget round-robins ONE chunk at a time across
        # them so one long prompt can't starve another's TTFT
        self._prefill_queue: list[int] = []
        # --- hybrid paged-KV bookkeeping (host-owned; the tick takes the
        # sliced table + lengths as plain arguments, so admission/evict
        # page moves are pure host work) ---
        # --- speculative decoding (serving/spec_decode.py; docs/
        # SERVING.md "Speculative decoding").  K = cfg.spec_tokens > 0
        # swaps the decode tick for a draft-verify tick: one
        # lm_verify_chunk launch of width W = K+1 per step, committing
        # the longest correct prefix (up to W+1 tokens) per full weight
        # read.  Greedy-only — submit() rejects top_k != 1.  K = 0 is
        # the byte-stable status quo: no spec state, no record stamps,
        # identical traces.
        self.spec = cfg.spec_tokens > 0
        if self.spec:
            # tokens_per_tick paces the NON-speculative tick; in spec
            # mode each step runs exactly one verify launch instead
            self.spec_width = cfg.spec_tokens + 1
            self.drafter = (drafter if drafter is not None
                            else spec_decode.make_drafter(cfg))
            self._spec_drafted = 0  # per-window gauges -> serving_tick
            self._spec_accepted = 0
            self._spec_streams = 0  # live slot-launches in the window
            # verify lanes the LAST tick computed: debited from the next
            # step's chunk-prefill budget so speculation's extra per-step
            # work is accounted against the same interleaving bound
            # (the serving_mfu / ITL honesty contract)
            self._spec_budget_debt = 0
            self.metrics.configure_speculation(
                cfg.spec_tokens, cfg.spec_drafter
            )
        else:
            self.drafter = None
        self.hybrid = bool(cfg.attn_layer_idx)
        # expert layers: every launch also returns its load (a held
        # expert's rows), read with the tick's fetch (_note_expert_load)
        self._moe = cfg.moe_num_experts > 0
        self._chunk_loads: list = []  # (span, device load, real tokens)
        if self.hybrid:
            self.page_pool = state_cache.PagePool(
                state_cache.hybrid_pool_pages(cfg, capacity,
                                              self.num_shards),
                num_shards=self.num_shards,
            )
            # spec mode appends one permanent trash column: the verify
            # chunk may write up to W tokens past a slot's reservation
            # (drafts beyond its budget), and those writes must clamp
            # onto a trash entry — never wrap onto the slot's own last
            # live page (attention_mixer_chunk clips page indices to
            # the table width)
            self._page_tbl = np.zeros(
                (capacity, cfg.kv_pages_per_slot + (1 if self.spec else 0)),
                np.int32,
            )
            self._kv_len = np.zeros((capacity,), np.int32)
            self._page_allocs = 0  # per-step gauges -> serving_tick
            self._page_frees = 0
        # --- prefix-state cache (serving/prefix_cache.py): host-side
        # LRU of chunk-boundary carries + full-prompt snapshots keyed
        # by prompt-prefix hash.  Off unless cfg.prefix_cache_entries
        # > 0 or an explicit instance is passed.  Hybrid entries pin
        # KV pages in THIS engine's pool (refcounts; the LRU's evict
        # hook decrefs), so hybrid caches are engine-private.
        if prefix_cache is None and cfg.prefix_cache_entries > 0:
            prefix_cache = PrefixCache(
                max_entries=cfg.prefix_cache_entries,
                max_bytes=cfg.prefix_cache_bytes,
                min_hits=cfg.prefix_min_chunk_hits,
            )
        self.prefix_cache = prefix_cache
        if prefix_cache is not None:
            if self.hybrid:
                prefix_cache.evict_hook = self._drop_entry_pages
                # bytes one physical page pins across every layer's K+V
                # pool — the KV share of an entry's byte accounting
                self._page_nbytes = int(sum(
                    x.nbytes // x.shape[1]
                    for x in jax.tree.leaves(
                        self.pool["state"]["attn_blocks"])
                ))
            self.metrics.configure_prefix_cache()
        # --- quantized serving (ops/quant.py; docs/SERVING.md
        # "Quantized serving"): resident-bytes gauges, installed only
        # when quant is on so bf16 engines' records/summaries stay
        # byte-stable.  weight bytes are the device-resident decoded
        # tree (int8 kernels + f32 scales when quantized); page-pool
        # bytes the hybrid KV pools incl. their scale arrays.
        self.quantized_weights = cfg.serving_weight_dtype == "int8"
        self.quantized_kv = self.hybrid and cfg.kv_quantized
        if self.quantized_weights or self.quantized_kv:
            from mamba_distributed_tpu.ops.quant import param_bytes

            self._weight_bytes = param_bytes(self._params)
            self._pool_bytes = (
                sum(int(x.nbytes) for x in
                    jax.tree.leaves(self.pool["state"]["attn_blocks"]))
                if self.hybrid else None
            )
            self._quant_stamp = {"weights": cfg.serving_weight_dtype,
                                 "kv": cfg.kv_page_dtype}
            self.metrics.configure_memory(
                weight_bytes=self._weight_bytes,
                page_pool_bytes=self._pool_bytes or 0,
                weight_dtype=cfg.serving_weight_dtype,
                kv_dtype=cfg.kv_page_dtype,
            )
        # --- the decode tick's lane ladder (docs/SERVING.md
        # "Occupancy-adaptive ticks"): every tick launches at the
        # narrowest rung that holds the decodable slots, the whole pool
        # being the last rung.  ``_rung`` indexes the rung in use (taken
        # wider at once, narrower only after RUNG_HYSTERESIS_TICKS ticks
        # that would have fitted); ``_warm_shapes`` holds the launch shapes
        # (hybrid: page-count buckets) whose whole ladder has been run.
        self._rungs = tick_rungs(capacity, self.num_shards)
        self._rung = 0
        self._shrink_streak = 0
        self._warm_shapes: set = set()
        # --- multi-tenant LoRA serving (serving/adapters.py; docs/
        # SERVING.md "Multi-tenant LoRA"): cfg.lora_max_adapters > 0
        # attaches bounded device factor pools to the decode params and
        # threads per-slot adapter ids through every launch.  Off
        # (default) is the byte-stable status quo: no pools, no record
        # stamps, identical traces.
        self.lora = cfg.lora_max_adapters > 0
        if self.lora:
            if self.quantized_weights:
                raise ValueError(
                    "int8 base weights + a LoRA delta is a ROADMAP "
                    "residual (the two dequant paths don't compose "
                    "yet): serve LoRA adapters with "
                    "serving_weight_dtype='bf16', or quantize without "
                    "lora_max_adapters"
                )
            self.adapters = (adapters if adapters is not None
                             else adapters_mod.AdapterRegistry(cfg, params))
            if self.adapters.rank != cfg.lora_rank:
                raise ValueError(
                    f"adapter registry rank {self.adapters.rank} != "
                    f"cfg.lora_rank {cfg.lora_rank} — the factor pools "
                    f"are static-shape; one rank per engine"
                )
            self.adapter_cache = adapters_mod.AdapterCache(
                self.adapters, cfg.effective_lora_cache_slots,
                compute_dtype=cfg.compute_dtype,
            )
            self._base_decode_params = self._params
            self._lora_version = -1
            self._refresh_lora_params()
            # window deltas for the tick-record gauges (the cache keeps
            # cumulative counters)
            self._ad_hits0 = 0
            self._ad_misses0 = 0
            self._ad_evictions0 = 0
            self.metrics.configure_adapters(
                cfg.lora_max_adapters, cfg.lora_rank,
                cfg.effective_lora_cache_slots,
            )
        else:
            self.adapters = None
            self.adapter_cache = None
        # --- per-tenant fairness quota + online-tuning hot swaps
        # (docs/SERVING.md "Online adapter tuning"): cfg.tenant_max_slots
        # caps the concurrent resident slots one tenant (adapter BASE
        # name — versions share the cap) may hold; an over-quota
        # admission requeues with the named TenantQuotaExceeded counted,
        # never shed.  0 (default) is the byte-stable status quo.
        self.tenant_max_slots = getattr(cfg, "tenant_max_slots", 0)
        self._quota_stalls = 0  # window counter -> tick records
        self._hot_swaps = 0  # mid-stream adapter version swaps, ditto
        if self.tenant_max_slots:
            self.metrics.configure_tuning()
        # --- durable session fabric (serving/sessions/; docs/SERVING.md
        # "Durable sessions"): an attached SessionStore lets streams
        # PARK — slot, KV pages and adapter ref all released, the
        # stream serialized into the migration artifact (+ its emitted
        # tokens) — and resume bit-exactly later, here or on any
        # replica.  The admission valve parks pressure victims through
        # it (full artifact to the tiered store, a tiny session-pointer
        # snapshot on the requeued tracker) instead of pinning their
        # carries in host RAM forever.  Off (default) is the
        # byte-stable status quo: no stamps, no spans, no sweeps.
        self.session_store = session_store
        self._session_parks = 0  # window counters -> tick records
        self._session_resumes = 0
        self._session_expires = 0
        if session_store is not None:
            self.metrics.configure_sessions()
        # recently finished streams' tokens (bounded), so a restarted
        # front end can re-attach an SSE stream whose final events died
        # with the old connection (stream_state; docs/SERVING.md
        # "Deploying as a service" — SSE resume tokens).  In-flight
        # streams replay from their trackers; this ring only covers the
        # just-finished tail.
        self._recent_finished: dict[int, tuple[list[int], str]] = {}
        self._pc_hits = 0  # per-window gauges -> serving_tick records
        self._pc_misses = 0
        self._pc_saved_tokens = 0
        self._preemptions = 0
        # disaggregated prefill/decode handoff (serving/router.py):
        # the hook a prefill-role replica's router installs, plus the
        # per-window migration counters -> serving_tick records
        self.migrate_hook = migrate_hook
        self._migrations_out = 0
        self._migrations_in = 0
        # prefill accounting awaiting a tick record: tick-less steps
        # (everything resident still mid-prefill) roll their stall /
        # chunk counters into the NEXT tick's jsonl record so the
        # serving_tick stream never drops work (obs_report.py totals)
        self._pending_stall_ms = 0.0
        self._pending_chunk_tokens = 0
        self._pending_chunk_real_tokens = 0  # non-pad (goodput useful)
        self._pending_chunk_ms = 0.0
        # one-shot (unchunked) admissions in the window: real prompt
        # tokens vs padded bucket lanes — without these the goodput
        # fields would credit a 33-token (chunked) prompt but not a
        # 32-token (one-shot) one over the same wall window
        self._pending_oneshot_real_tokens = 0
        self._pending_oneshot_lanes = 0
        self.results: dict[int, GenerationResult] = {}

    # ------------------------------------------------------------- admission

    @property
    def tracer(self):
        return self._tracer

    @tracer.setter
    def tracer(self, tracer) -> None:
        # the engine's spans go on the profiler's clock too
        # (obs/tracer.AnnotatedTracer); wrapped once, here
        self._tracer = annotated(tracer)

    def submit(self, request: GenerationRequest) -> int:
        """Queue a request; returns its request_id."""
        return self._submit_tracked(request).request_id

    def _submit_tracked(self, request: GenerationRequest) -> _Tracked:
        """``submit`` returning the scheduler's tracker itself (what
        ``submit_migrated`` decorates with the migration artifact)."""
        if not 1 <= request.top_k <= self.max_top_k:
            raise ValueError(
                f"request top_k={request.top_k} must be in "
                f"[1, max_top_k={self.max_top_k}]"
            )
        if self.spec and request.top_k != 1:
            raise ValueError(
                f"speculative decoding (cfg.spec_tokens="
                f"{self.cfg.spec_tokens}) is greedy-only: request "
                f"top_k={request.top_k} must be 1 (argmax).  Sampling-"
                f"mode rejection sampling is a ROADMAP residual; serve "
                f"sampled requests on a spec_tokens=0 engine"
            )
        adapter = getattr(request, "adapter", None)
        if adapter:
            if not self.lora:
                raise ValueError(
                    f"request names adapter {adapter!r} but this engine "
                    f"serves the base model only "
                    f"(cfg.lora_max_adapters=0); enable multi-tenant "
                    f"LoRA serving (docs/SERVING.md) or drop the "
                    f"adapter field"
                )
            if adapter not in self.adapters:
                # the NAMED error, at submit — never a hang, and the
                # HTTP front end maps it to a 404 (serving/adapters.py)
                raise adapters_mod.UnknownAdapterError(
                    f"unknown adapter {adapter!r}: this engine's "
                    f"registry holds {self.adapters.names()}"
                )
            # pin the VERSION at submit: a bare name canonicalizes to
            # its latest registered version (the identity for a single-
            # version adapter — bytes unchanged vs PR-15), so a v(N+1)
            # registered mid-flight never silently retargets an
            # already-queued stream (prefix salt, cache slot, records
            # and failover replay all carry the pinned name).  With
            # cfg.lora_ab_fraction < 1 the pin A/B-routes across the
            # last two versions (_ab_resolve)
            request.adapter = self._ab_resolve(request, adapter)
        if self.hybrid:
            need = len(request.prompt_ids) + request.max_new_tokens
            if need > self.cfg.kv_slot_tokens:
                raise ValueError(
                    f"hybrid request needs {need} KV tokens (prompt + "
                    f"max_new_tokens) > cfg.kv_slot_tokens="
                    f"{self.cfg.kv_slot_tokens}; raise the knob or split "
                    f"the request"
                )
            need_pages = attention_page_count(self.cfg, need)
            if need_pages > self._max_shard_pages():
                # an oversubscribed pool (kv_pool_pages < slots * pages)
                # may be smaller than one slot's budget — and a SHARDED
                # pool confines each slot to its own shard's page range:
                # admission waits for frees, so a request bigger than
                # any shard could EVER free would stall the queue
                # forever — reject it up front (the same check guards
                # _admit for requests that bypass submit)
                raise ValueError(
                    f"hybrid request needs {need_pages} KV pages but the "
                    f"page pool's widest shard only holds "
                    f"{self._max_shard_pages()} "
                    f"({self.page_pool.num_pages} total over "
                    f"{self.num_shards} shard(s); cfg.kv_pool_pages); "
                    f"it could never be admitted"
                )
        return self.scheduler.submit(request)

    def submit_migrated(self, request: GenerationRequest, snapshot: dict,
                        *, source_replica: int | None = None) -> int:
        """Admit a request mid-journey: it finished prefill on ANOTHER
        replica (the prefill tier, docs/SERVING.md "Disaggregated
        tiers") and arrives as the O(1) migration artifact — conv/SSM
        carry + last logits, plus serialized hybrid KV page contents —
        instead of a prompt to prefill.  Queued like any request
        (same validation, same FCFS/priority order); admission routes
        it through the ``state_cache.restore`` path (zero prefill
        compute here, fresh pages allocated and the serialized KV
        scattered in), and the resumed stream is bit-exactly the one
        a local prefill would have produced.  Latency stamps span the
        WHOLE journey: ``snapshot["t_submit"]`` carries the original
        submit time, so the finished record's TTFT/e2e include the
        prefill-tier residency.  Returns the engine-local request id."""
        tracked = self._submit_tracked(request)
        tracked.snapshot = snapshot
        tracked.no_migrate = True  # never bounce back to a prefill tier
        tracked.migration_source = source_replica
        # a PARKED session's artifact additionally carries the tokens
        # already streamed to the client (a migration artifact never
        # does — migration happens before the first token): restore
        # them so the resumed stream CONTINUES — token indices, the
        # max_new_tokens budget and the artifact's ``step`` all line up
        # with the park point instead of replaying from zero
        prior = snapshot.get("new_tokens")
        if prior:
            tracked.new_tokens.extend(int(t) for t in prior)
        # a hot-swapped stream's artifact carries its step re-base (the
        # request arriving here is already the continuation, so future
        # preempt/park stamps keep subtracting it); absent = 0
        tracked.swap_base = int(snapshot.get("swap_base", 0))
        now = time.perf_counter()
        if snapshot.get("t_submit_age_s") is not None:
            # cross-host-safe: reconstruct the original stamps on THIS
            # host's monotonic clock from their ages at packaging (raw
            # perf_counter values don't transport between hosts);
            # t_admit is localized in place so the restore path's
            # existing read consumes it unchanged
            tracked.t_submit = now - snapshot["t_submit_age_s"]
            if snapshot.get("t_admit_age_s") is not None:
                snapshot["t_admit"] = now - snapshot["t_admit_age_s"]
        elif snapshot.get("t_submit") is not None:
            tracked.t_submit = snapshot["t_submit"]
        return tracked.request_id

    # finished streams whose token lists stay replayable for SSE resume
    # (stream_state) after eviction — a small host-side ring
    RECENT_FINISHED_KEEP = 128

    def stream_state(self, request_id: int,
                     from_index: int = 0) -> dict | None:
        """Replay view of one stream for a re-attaching consumer (the
        SSE resume path, docs/SERVING.md "Deploying as a service"):
        ``{"tokens": <emitted[from_index:]>, "done", "finish_reason",
        "request"}`` for an in-flight (resident, queued or preempted)
        request — whose tokens live on its tracker — or a recently
        finished one (the bounded ``RECENT_FINISHED_KEEP`` ring;
        ``request`` is None there).  None for an unknown id.  Pure
        host-side bookkeeping: no device sync, no stream perturbation,
        and the engine keeps generating whether or not anyone
        re-attaches."""
        for t in list(self._slots.values()) + list(self.scheduler):
            if t.request_id == request_id:
                return {
                    "tokens": list(t.new_tokens[from_index:]),
                    "done": False,
                    "finish_reason": None,
                    "request": t.request,
                }
        fin = self._recent_finished.get(request_id)
        if fin is not None:
            toks, reason = fin
            return {
                "tokens": list(toks[from_index:]),
                "done": True,
                "finish_reason": reason,
                "request": None,
            }
        return None

    def withdraw_queued(self) -> list[int]:
        """Pull every queued-but-UNSTARTED request (status QUEUED, no
        resume/migration snapshot) out of the admission queue and
        return their request ids — the drain shutdown path
        (``EngineReplica.drain(requeue=True)``): the router re-places
        withdrawn work on surviving replicas instead of stranding it
        behind a retiring engine's queue.  Requests already holding a
        slot, a preemption snapshot, or a migrated-in artifact are NOT
        withdrawn — their state lives here and finishes here."""
        return [t.request_id for t in self.scheduler.withdraw_unstarted()]

    def _seed_spec(self, tracked: _Tracked, logits) -> None:
        """Seed a freshly-decodable slot's pending queue with the greedy
        argmax of its prefill logits — the exact token the first
        non-speculative tick would emit, and the anchor the drafter
        needs to propose continuations.  The ``np.asarray`` fetch is
        the one extra host sync speculation costs per REQUEST (every
        subsequent next-token comes back inside the tick's own greedy
        fetch).  No-op when speculation is off."""
        if not self.spec:
            return
        tracked.spec_pending = [spec_decode.greedy_token(
            np.asarray(logits).reshape(-1), self.cfg.vocab_size
        )]
        tracked.spec_pending_emitted = 0

    # ------------------------------------------------ multi-tenant LoRA

    def _refresh_lora_params(self) -> None:
        """Re-attach the adapter cache's factor pools to the decode
        params after a pool write (upload/evict — ``AdapterCache.
        version``).  Pure host-side tree surgery plus, on a mesh, a
        device_put that is a no-op for every already-placed base leaf;
        the compiled launches see the pools as ordinary param leaves,
        so one trace serves every resident-adapter mix."""
        if self.adapter_cache.version == self._lora_version:
            return
        p = adapters_mod.attach_adapter_pools(
            self._base_decode_params, self.adapter_cache.pools
        )
        if self.mesh is not None:
            from mamba_distributed_tpu.parallel.sharding import (
                serving_param_shardings,
            )

            p = jax.device_put(p, serving_param_shardings(p, self.mesh))
        self._params = p
        self._lora_version = self.adapter_cache.version

    def adapter_resident(self, name: str) -> bool:
        """Is ``name``'s factor set on this engine's device cache right
        now?  A pure probe — the router's adapter-affinity placement
        term reads it (serving/replica.place_cost)."""
        return (self.lora and self.adapter_cache.resident(name))

    def _ab_resolve(self, request, adapter: str) -> str:
        """Submit-time version pin with A/B routing.

        Identity with ``cfg.lora_ab_fraction >= 1`` (default — the
        plain ``resolve`` pin, bytes unchanged vs PR-15).  Below 1, a
        BARE name on a tenant with >= 2 registered versions routes
        only that fraction of new submits to the latest version; the
        rest pin the PREVIOUS one — the control arm of an online-tune
        deploy.  The arm choice hashes the request's identity (adapter
        base, sampling seed, prompt bytes — crc32, not ``hash()``,
        which is per-process randomized), so a resubmitted request
        lands on the same arm on every replica.  Explicit ``@vN``
        names bypass: a pinned version is an explicit routing decision.
        """
        frac = getattr(self.cfg, "lora_ab_fraction", 1.0)
        base, ver = adapters_mod.split_adapter_version(adapter)
        if frac >= 1.0 or ver is not None:
            return self.adapters.resolve(adapter)
        latest = self.adapters.version_of(base)
        if latest < 2:
            return self.adapters.resolve(adapter)
        prev_key = adapters_mod.versioned_name(base, latest - 1)
        if prev_key not in self.adapters:
            # forward version jump (e.g. a late-joining replica got
            # @v3 but never held v2): no control arm to route to
            return self.adapters.resolve(adapter)
        import zlib

        h = zlib.crc32(
            np.asarray(request.prompt_ids, np.int32).tobytes(),
            zlib.crc32(f"{base}:{request.seed}".encode("utf-8")),
        )
        if (h % 10_000) < int(frac * 10_000):
            return adapters_mod.versioned_name(base, latest)
        return prev_key

    def _adapter_salt(self, request) -> bytes:
        """Prefix-cache key salt for one request's adapter identity —
        carry snapshots depend on the adapter delta that shaped them,
        so a warm hit under adapter X must never seed adapter Y.
        ``b""`` on LoRA-less engines and adapter-less requests: keys
        byte-identical to pre-LoRA."""
        if not self.lora:
            return b""
        return adapters_mod.prefix_salt(getattr(request, "adapter", None))

    def _acquire_adapter_ref(self, tracked: _Tracked) -> bool:
        """Reserve the request's adapter factor slot (the admission
        analogue of the KV page reservation).  True = ready —
        ``tracked.adapter_slot`` holds the pool row (0 = no adapter);
        False = every cache slot is pinned by other resident streams:
        the caller requeues and admission waits, exactly like a short
        page pool — never a mid-flight miss."""
        if not self.lora or not getattr(tracked.request, "adapter", None):
            tracked.adapter_slot = 0
            return True
        if tracked.adapter_slot:  # preempted resume: the ref rode along
            return True
        slot = self.adapter_cache.acquire(tracked.request.adapter)
        if slot is None:
            return False
        tracked.adapter_slot = slot
        self._refresh_lora_params()  # a miss uploaded fresh pool rows
        return True

    def _lora_call_kw(self, tracked: _Tracked) -> dict:
        """The ``adapter_ids=`` kwarg for a batch-1 prefill/chunk
        launch — EMPTY on LoRA-less engines: even an explicit
        ``adapter_ids=None`` would change the jit cache key vs a
        caller that omits it (solo ``generate()``'s chunk driver),
        splitting the one shared chunk trace the parity contract
        leans on.  LoRA engines always pass the (1,) array, row 0
        (the zero factors) for adapter-less requests, so one trace
        serves every adapter mix."""
        if not self.lora:
            return {}
        return {"adapter_ids": jnp.full((1,), tracked.adapter_slot or 0,
                                        jnp.int32)}

    def _release_adapter_ref(self, tracked: _Tracked) -> None:
        """Drop the request's adapter-slot ref (finish, failure,
        migrate-out, failed admission requeue).  Idempotent via the
        ``adapter_slot`` sentinel — the cache itself raises the named
        ``AdapterCacheError`` on a genuine double release."""
        if self.lora and tracked.adapter_slot:
            self.adapter_cache.release(tracked.request.adapter)
        tracked.adapter_slot = None

    def _slot_shard(self, slot: int) -> int:
        """Which data shard holds ``slot``'s pool rows (NamedSharding
        partitions the slot axis contiguously)."""
        return slot * self.num_shards // self.capacity

    def _max_shard_pages(self) -> int:
        """The most KV pages any one shard could EVER have free — the
        upper bound on a single request's reservation (each slot draws
        only from its own shard's range)."""
        return max(self.page_pool.shard_capacity(d)
                   for d in range(self.num_shards))

    def _release_pages(self, slot: int, tracked: _Tracked) -> None:
        """Recycle a slot's KV pages (evict/failure): return them to the
        allocator and point the slot's table row at the trash page so
        nothing it computes can ever touch a recycled page."""
        if not (self.hybrid and tracked.pages):
            return
        self.page_pool.free(tracked.pages)
        self._page_frees += len(tracked.pages)
        tracked.pages = None
        self._page_tbl[slot] = 0
        self._kv_len[slot] = 0

    def _admit(self, tracked: _Tracked) -> bool:
        """Grant the next queued request a slot.  Short pure-SSM prompts
        prefill one-shot right here (PR-1 path); long prompts — and ALL
        hybrid prompts, whose chunk step writes straight into the paged
        KV pool — register a chunk plan and park a zero carry, their
        chunks running in the budget phase (``_advance_prefill``).

        With the prefix cache on, admission first matches the longest
        cached chunk-aligned prefix of the prompt: a FULL hit inserts
        the snapshot's state+logits outright (zero chunk steps — the
        near-zero-TTFT path), a partial hit seeds the carry so prefill
        resumes at the first uncached chunk.  Hybrid hits attach to the
        cached prefix's KV pages copy-on-write (read-only refs on whole
        pages, a fresh device copy of the boundary page the slot will
        append into), confined to the prefix's data shard.

        A preempted request (``tracked.snapshot``) re-admits through
        ``_resume`` instead — host carry restored, no prefill at all.

        Returns False (request back at the queue head, admission stalls)
        when a hybrid request's page reservation doesn't fit the free
        pool yet — evictions recycle pages, never a mid-flight OOM."""
        if tracked.snapshot is not None:
            return self._resume(tracked)
        r = tracked.request
        # per-tenant fairness quota (cfg.tenant_max_slots): a tenant at
        # its concurrent-slot cap WAITS in the queue — the page-stall
        # idiom (requeue + retry next step), named and counted, never
        # shedding.  Resumes bypass this check (they held a slot
        # before; blocking a snapshot-holder could strand its state).
        if self.tenant_max_slots:
            try:
                check_tenant_quota(
                    getattr(r, "adapter", None),
                    (getattr(t.request, "adapter", None)
                     for t in self._slots.values()),
                    self.tenant_max_slots,
                )
            except TenantQuotaExceeded:
                self._quota_stalls += 1
                self.metrics.record_quota_stall()
                self.scheduler.requeue(tracked)
                return False
        # multi-tenant LoRA: reserve the adapter's factor slot FIRST
        # (the page-reservation discipline) — when every cache slot is
        # pinned by other resident streams the request waits in the
        # queue, and finishing streams release slots, so admission can
        # never miss factors mid-flight
        if not self._acquire_adapter_ref(tracked):
            self.scheduler.requeue(tracked)
            return False
        salt = self._adapter_salt(r)
        plan = plan_chunks(len(r.prompt_ids),
                           self.cfg.effective_prefill_chunk_tokens,
                           force=self.hybrid)
        # PEEK: stats/recency/promotion commit only after a slot is
        # secured (commit_lookup below) — a page-stalled request retries
        # this every step and must not drift the cache's counters
        hit = (None if self.prefix_cache is None
               else self.prefix_cache.lookup(r.prompt_ids, plan,
                                             peek=True, salt=salt))
        n_pages = shared_n = fresh_n = 0
        cow = False
        if self.hybrid:
            n_pages = attention_page_count(
                self.cfg, len(r.prompt_ids) + r.max_new_tokens
            )
            if n_pages > self._max_shard_pages():
                # DEADLOCK check: free + in-flight reservations is all a
                # shard can ever hold, so this reservation could never
                # be satisfied by future evictions — waiting would stall
                # the queue forever.  submit() rejects such requests up
                # front; this guards ones fed past it (e.g. straight
                # into the scheduler).  The request is DROPPED, not
                # requeued: requeueing would park the poison request at
                # the queue head and re-raise on every subsequent
                # step(), starving everything behind it.
                raise RuntimeError(
                    f"request {tracked.request_id} needs {n_pages} KV "
                    f"pages but no shard's pool exceeds "
                    f"{self._max_shard_pages()} pages even with every "
                    f"in-flight reservation evicted "
                    f"({self.page_pool.num_pages} usable pages over "
                    f"{self.num_shards} shard(s)) — it can never be "
                    f"admitted and has been dropped from the queue; "
                    f"raise cfg.kv_pool_pages or split the request"
                )
            if hit is not None:
                # a cached prefix's pages live in ONE data shard (pages
                # never cross shards); attaching needs a same-shard slot
                # plus fresh pages for everything this slot will write —
                # whole shared pages stay read-only, and a prefix ending
                # mid-page costs one extra fresh page for the CoW copy
                entry = hit[0]
                page = self.cfg.kv_page_tokens
                shared_n = entry.kv_len // page
                cow = bool(entry.kv_len % page)
                fresh_n = n_pages - shared_n
                slot = next(
                    (s for s in self._free
                     if self._slot_shard(s) == entry.shard
                     and fresh_n <= self.page_pool.free_pages_in(
                         entry.shard)),
                    None,
                )
                if slot is None:
                    hit = None  # serve cold rather than wait on one
                    # shard (commit_lookup below records the miss: the
                    # work gets fully recomputed)
            if hit is None:
                # first free slot whose shard can cover the reservation
                # (a sharded pool confines each slot to its shard's
                # pages; unsharded pools have one shard, preserving FCFS
                # slot order)
                def _fits():
                    return next(
                        (s for s in self._free
                         if n_pages <= self.page_pool.free_pages_in(
                             self._slot_shard(s))),
                        None,
                    )

                slot = _fits()
                if slot is None and self._reclaim_cache_pages(n_pages):
                    slot = _fits()
                if slot is None:
                    # page-stalled: drop the adapter ref too, so a
                    # withdrawn (drained-away) queued request can't
                    # strand a factor slot; the retry re-acquires
                    self._release_adapter_ref(tracked)
                    self.scheduler.requeue(tracked)
                    return False
            self._free.remove(slot)
        else:
            slot = self._free.pop(0)
        tracked.status = RequestStatus.PREFILL
        entry = hit[0] if hit is not None else None
        seeded_chunks = hit[1] if hit is not None else 0
        full_hit = entry is not None and entry.full
        t0 = time.perf_counter()
        try:
            if self.hybrid and entry is not None:
                fresh = self.page_pool.alloc(fresh_n, entry.shard)
                shared = list(entry.kv_pages[:shared_n])
                self.page_pool.incref(shared)
                # the gauges count page REFS acquired/released (incref
                # included) so allocs == frees still closes the loop on
                # cache-sharing engines — _release_pages decrefs every
                # ref this slot holds, shared or fresh
                self._page_allocs += fresh_n + len(shared)
                tracked.pages = shared + fresh
                if cow:
                    # the slot's first KV write targets position kv_len,
                    # inside the prefix's last (partial) page: append
                    # into an owned copy, never the shared original
                    self.pool["state"]["attn_blocks"] = \
                        state_cache.copy_page(
                            self.pool["state"]["attn_blocks"],
                            int(entry.kv_pages[shared_n]), int(fresh[0]),
                        )
                self._page_tbl[slot] = 0
                self._page_tbl[slot, :n_pages] = tracked.pages
                self._kv_len[slot] = entry.kv_len
            if full_hit:
                # the snapshot IS the prefill's output: insert it and
                # decode — zero chunk steps, zero prefill compute (the
                # next tick's fetch is the one sync point, as ever)
                with self.tracer.span("serving_prefill", slot=slot,
                                      request=tracked.request_id,
                                      trace=tracked.trace_id,
                                      cache="full"):
                    self.pool = state_cache.insert(
                        self.pool, slot,
                        {"blocks": entry.state["blocks"]}, entry.logits,
                        r.resolve_key(), r.max_new_tokens, r.top_k,
                        r.temperature,
                        -1 if r.eos_id is None else r.eos_id,
                        adapter_id=tracked.adapter_slot or 0,
                    )
                    self._seed_spec(tracked, entry.logits)
            elif entry is not None:
                # partial hit: seed the cached carry; chunking resumes
                # at the first uncached chunk (the remaining chunks run
                # the identical computation a cold admission would, so
                # the warm stream is bit-identical to cold)
                tracked.plan = plan
                tracked.chunks_done = seeded_chunks
                tracked.prefill_dt = 0.0
                tracked.prefill_seeded_tokens = entry.tokens
                self.pool = state_cache.stash_prefill(
                    self.pool, slot, {"blocks": entry.state["blocks"]},
                    r.resolve_key(), r.max_new_tokens, r.top_k,
                    r.temperature, -1 if r.eos_id is None else r.eos_id,
                    adapter_id=tracked.adapter_slot or 0,
                )
            elif plan is None:
                # one per-request span (trace-stamped) so even a short
                # prompt's journey has an anchor in this replica's
                # stream for the exporter's flow arrows
                with self.tracer.span("serving_prefill", slot=slot,
                                      request=tracked.request_id,
                                      trace=tracked.trace_id):
                    prompt = jnp.asarray(r.prompt_ids, jnp.int32)[None, :]
                    padded, mask = pad_to_bucket(
                        prompt, next_pow2_bucket(prompt.shape[1])
                    )
                    # async dispatch: admitting k queued requests between
                    # ticks queues k prefills+inserts without a host sync
                    # each — the next tick's token fetch is the one
                    # synchronization point
                    logits, state = _prefill(
                        self._params, padded, mask, cfg=self.cfg,
                        mesh=self._tp_mesh,
                        **self._lora_call_kw(tracked),
                    )
                    self.pool = state_cache.insert(
                        self.pool, slot, state, logits, r.resolve_key(),
                        r.max_new_tokens, r.top_k, r.temperature,
                        -1 if r.eos_id is None else r.eos_id,
                        adapter_id=tracked.adapter_slot or 0,
                    )
                    self._seed_spec(tracked, logits)
                    if self.prefix_cache is not None:
                        # snapshot the one-shot prefill's output (state
                        # was NOT donated by insert — safe to retain):
                        # an exact prompt repeat skips _prefill outright
                        self.prefix_cache.maybe_store_full(
                            r.prompt_ids, state, logits, salt=salt
                        )
            else:
                tracked.plan = plan
                tracked.chunks_done = 0
                tracked.prefill_dt = 0.0
                if self.hybrid:
                    tracked.pages = self.page_pool.alloc(
                        n_pages, self._slot_shard(slot)
                    )
                    self._page_allocs += n_pages
                    self._page_tbl[slot] = 0
                    self._page_tbl[slot, :n_pages] = tracked.pages
                    self._kv_len[slot] = 0
                self.pool = state_cache.stash_prefill(
                    self.pool, slot,
                    {"blocks": init_lm_blocks_state(self.cfg, batch=1)},
                    r.resolve_key(), r.max_new_tokens, r.top_k,
                    r.temperature, -1 if r.eos_id is None else r.eos_id,
                    adapter_id=tracked.adapter_slot or 0,
                )
        except Exception:
            # a failed prefill must neither leak the slot (capacity would
            # shrink for the process lifetime) nor drop the request — it
            # goes back to the queue head so a caller catching the raise
            # still sees it in `pending` and can retry or cancel
            self._release_pages(slot, tracked)
            self._release_adapter_ref(tracked)
            self._free.insert(0, slot)
            self.scheduler.requeue(tracked)
            raise
        if self.prefix_cache is not None:
            # admission went through: commit the lookup outcome — cache
            # lifetime stats/recency/promotion + the engine's window
            # gauges.  AFTER the try block, so a failed (requeued +
            # retried) admission can't double-count, and a shard-
            # dropped hybrid hit commits as the miss it became.
            self.prefix_cache.commit_lookup(r.prompt_ids, plan, hit,
                                            salt=salt)
            kind = None if entry is None else (
                "full" if full_hit else "partial")
            tracked.cache_hit = kind
            if kind is None:
                self._pc_misses += 1
            else:
                self._pc_hits += 1
                self._pc_saved_tokens += entry.tokens
            self.metrics.record_prefix_lookup(
                kind, 0 if entry is None else entry.tokens)
        # dt is host dispatch time (prefill runs async; the next tick's
        # fetch absorbs device completion)
        t_admit = time.perf_counter()
        if plan is None and entry is None:
            self.metrics.record_prefill(int(len(r.prompt_ids)), t_admit - t0)
            # goodput: the one-shot prefill's real tokens vs the padded
            # bucket lanes it computed, attributed to the next tick's
            # window (its dispatch time is already in the stall).  A
            # full-hit admission ran NO prefill lanes, so it counts in
            # neither side — its win shows up as prefix_saved_tokens.
            self._pending_oneshot_real_tokens += int(len(r.prompt_ids))
            self._pending_oneshot_lanes += next_pow2_bucket(
                len(r.prompt_ids)
            )
        # lifecycle stamps: queue-wait is submit -> slot granted; the
        # per-request ITL histogram rides in the finish record so
        # obs_report.py can merge per-token percentiles across requests
        tracked.t_admit = t_admit
        if full_hit or plan is None:
            tracked.t_prefill_done = t_admit  # nothing left to prefill
        tracked.itl_hist = StreamingHistogram()
        self.metrics.record_queue_wait(t_admit - tracked.t_submit)
        tracked.slot = slot
        self._slots[slot] = tracked
        if full_hit or plan is None:
            tracked.status = RequestStatus.DECODE
        else:
            self._prefill_queue.append(slot)
        return True

    def _advance_prefill(self, slot: int, budget_left: float) -> float:
        """Run ONE chunk of ``slot``'s partial prefill (the budget loop
        round-robins single chunks across concurrent prefills, so the
        caller controls fairness).  Completion flips the slot decodable;
        otherwise the carry is re-stashed.  Returns the remaining
        budget."""
        tracked = self._slots[slot]
        plan, r = tracked.plan, tracked.request
        try:
            state = state_cache.read_state(self.pool, slot)
            if self.hybrid:
                # the chunk step writes THIS slot's pages in the shared
                # pool directly (donated through the call): compose the
                # full carry from the pool pages + the host-owned
                # table row / length
                state["attn_blocks"] = self.pool["state"]["attn_blocks"]
                # copies, not views of the host mirrors: on the CPU
                # backend jnp.asarray aliases a 64-byte-aligned host
                # buffer instead of copying it, the chunk step below is
                # only DISPATCHED here, and the length mirror advances
                # right after — a still-queued step would read the
                # advanced length (wrong tokens, depending on where
                # numpy happened to allocate the mirror)
                state["attn_meta"] = (
                    jnp.asarray(self._page_tbl[slot : slot + 1].copy()),
                    jnp.asarray(self._kv_len[slot : slot + 1].copy()),
                )
            i = tracked.chunks_done
            ids, mask = chunk_inputs(r.prompt_ids, plan, i)
            t0 = time.perf_counter()
            with self.tracer.span("serving_prefill_chunk", slot=slot,
                                  chunk=i, of=plan.n_chunks,
                                  trace=tracked.trace_id) as span:
                logits, state, *load = prefill_chunk(
                    self._params, ids, mask, state, cfg=self.cfg,
                    mesh=self._tp_mesh, return_load=self._moe,
                    **self._lora_call_kw(tracked),
                )
                if load:
                    # only dispatched here: read when the next tick's
                    # fetch has returned (_note_expert_load)
                    self._chunk_loads.append(
                        (span, load[0], plan.real_tokens(i)))
                if self.hybrid:
                    # pages were written in place (donated): swap the
                    # fresh buffers into the pool IMMEDIATELY — before
                    # any tracer/metrics host work can raise — so the
                    # except path below never touches donated-away
                    # buffers; advance the host-side length mirror by
                    # this chunk's REAL tokens (the left pad of chunk 0
                    # is never written)
                    self.pool["state"]["attn_blocks"] = state["attn_blocks"]
                    self._kv_len[slot] += plan.real_tokens(i)
            dt = time.perf_counter() - t0  # host dispatch time
            tracked.chunks_done += 1
            tracked.prefill_dt += dt
            budget_left -= plan.chunk
            self.metrics.record_prefill_chunk(plan.chunk, dt)
            # goodput: real (non-pad) chunk tokens are the useful share
            # of this window's prefill lanes
            self._pending_chunk_real_tokens += plan.real_tokens(i)
            state = {"blocks": state["blocks"]}
            # prefix cache: snapshot this boundary's carry (the arrays
            # are chunk-step OUTPUTS — the next grant resumes from the
            # pool via read_state, so nothing ever donates them away).
            # The LAST boundary is stored too: it seeds longer prompts
            # with the same left-pad that extend this one.
            salt = self._adapter_salt(r)
            self._store_prefix(r.prompt_ids, plan, i, state, slot,
                               salt=salt)
            if tracked.chunks_done == plan.n_chunks:
                # ...and the full-prompt entry (state + last logits):
                # an exact repeat skips prefill entirely
                self._store_prefix(r.prompt_ids, plan, i, state, slot,
                                   logits=logits, salt=salt)
                self.pool = state_cache.finish_prefill(
                    self.pool, slot, state, logits
                )
                self._seed_spec(tracked, logits)
                self._prefill_queue.remove(slot)
                tracked.status = RequestStatus.DECODE
                tracked.t_prefill_done = time.perf_counter()
                # a partial hit seeded prefill_seeded_tokens of this
                # prompt from the cache — report only the COMPUTED
                # share (the seeded share is already accounted as
                # prefix_saved_tokens; counting it here too would
                # inflate prefill throughput on warm workloads)
                self.metrics.record_prefill(
                    plan.prompt_len - tracked.prefill_seeded_tokens,
                    tracked.prefill_dt,
                )
            else:
                self.pool = state_cache.stash_prefill(
                    self.pool, slot, state, r.resolve_key(),
                    r.max_new_tokens, r.top_k, r.temperature,
                    -1 if r.eos_id is None else r.eos_id,
                    adapter_id=tracked.adapter_slot or 0,
                )
                # rotate to the back: the NEXT chunk grant (this step or
                # the next) goes to the other in-flight prefills first —
                # round-robin across ticks, not just within one pass
                self._prefill_queue.remove(slot)
                self._prefill_queue.append(slot)
        except Exception:
            # mirror the one-shot contract: free the slot (and its KV
            # pages), requeue the request (restarting its prefill from
            # chunk 0), re-raise.  This recovery covers host- and
            # trace-time failures (bad inputs, retrace errors) — the
            # donated buffers are still intact then.  A RUNTIME device
            # failure inside a dispatched step poisons the donated pool
            # buffers (here via the chunk step's state donation, exactly
            # as it would via the tick's own pool donation) — that class
            # has never been recoverable engine-side and surfaces as
            # deleted-array errors on the next use.
            self.pool = state_cache.evict(self.pool, slot)
            self._release_pages(slot, tracked)
            self._release_adapter_ref(tracked)
            self._prefill_queue.remove(slot)
            del self._slots[slot]
            self._free.insert(0, slot)
            self._free.sort()
            tracked.plan = None
            tracked.chunks_done = 0
            tracked.slot = None
            self.scheduler.requeue(tracked)
            raise
        return budget_left

    # ------------------------------------------------- prefix-state cache

    def _drop_entry_pages(self, entry) -> None:
        """Prefix-cache LRU evict hook: release the entry's pinned KV
        page refs.  A page frees only when no slot still shares it
        (PagePool refcounts) — eviction decrefs, never yanks."""
        if entry.kv_pages:
            self.page_pool.free(list(entry.kv_pages))
            self._page_frees += len(entry.kv_pages)

    def _store_prefix(self, prompt_ids, plan, i: int, state: dict, slot,
                      logits=None, salt: bytes = b"") -> None:
        """Snapshot chunk ``i``'s carry into the prefix cache (with
        ``logits``: the full-prompt entry instead).  Hybrid snapshots
        pin the KV pages covering the prefix (incref — the cache is a
        holder like any slot; its evict hook decrefs).  ``state`` must
        be retainable: batch-1 device arrays no later call donates."""
        pc = self.prefix_cache
        if pc is None:
            return
        if logits is not None:
            key = prefix_cache_mod.full_key(prompt_ids, plan.chunk, salt)
            tokens = plan.prompt_len
        else:
            key = prefix_cache_mod.boundary_key(prompt_ids, plan, i, salt)
            tokens = (i + 1) * plan.chunk - plan.pad
        if not pc.wants(key):
            return
        kv_pages = None
        kv_len = shard = page_bytes = 0
        if self.hybrid:
            kv_len = tokens
            n = -(-kv_len // self.cfg.kv_page_tokens)
            kv_pages = tuple(self._slots[slot].pages[:n])
            self.page_pool.incref(list(kv_pages))
            self._page_allocs += n  # ref acquired (balances the evict
            # hook's decref in the kv_page_allocs/frees gauges)
            shard = self._slot_shard(slot)
            page_bytes = n * self._page_nbytes
        nbytes = (prefix_cache_mod.state_nbytes(state) + page_bytes
                  + (int(logits.nbytes) if logits is not None else 0))
        pc.put(key, prefix_cache_mod.PrefixEntry(
            state=state, tokens=tokens, chunks=i + 1, nbytes=nbytes,
            logits=logits, kv_pages=kv_pages, kv_len=kv_len, shard=shard,
        ))

    def _reclaim_cache_pages(self, n_pages: int) -> bool:
        """Admission pressure valve: the queue head needs KV pages that
        prefix-cache entries are pinning.  Evict page-pinned entries
        LRU-first (their hooks decref; a page actually frees only when
        no slot still shares it) until some free slot's shard covers
        the reservation.  Without this, non-resident holders could
        starve hybrid admission forever — resident slots always finish
        and release pages, cache entries never would.  Returns True
        when the reservation now fits somewhere."""
        pc = self.prefix_cache
        if pc is None or not self._free:
            return False

        shards = {self._slot_shard(s) for s in self._free}

        def satisfied():
            return any(n_pages <= self.page_pool.free_pages_in(d)
                       for d in shards)

        while not satisfied():
            if not pc.evict_one_pinned(shards):
                return False
        return True

    def _resume_parked(self) -> None:
        """Resume queued PREEMPTED requests into remaining free slots
        even though the queue's best request is stalled on KV pages:
        their swap-ins need no new pages (the refs ride on their
        trackers), and running them to completion is the only way the
        pages they pin ever release — without this, a stalled head
        and a page-holding preempted request behind it deadlock each
        other."""
        while self._free:
            parked = self.scheduler.pop_preempted()
            if parked is None or not self._admit(parked):
                return

    def prefix_hit_fraction(self, prompt_ids, adapter=None) -> float:
        """Fraction of ``prompt_ids`` whose prefill this engine's prefix
        cache could skip right now (0.0 with the cache off) — a pure
        probe: no stats bumped, no LRU recency touched.  The router's
        placement cost subtracts it (cache affinity: a warm replica is
        cheaper than an idle cold one for a shared-prefix prompt).
        ``adapter`` keys the probe to the request's LoRA identity —
        snapshots under another adapter are not hits for this one."""
        pc = self.prefix_cache
        if pc is None or len(prompt_ids) == 0:
            return 0.0
        plan = plan_chunks(len(prompt_ids),
                           self.cfg.effective_prefill_chunk_tokens,
                           force=self.hybrid)
        hit = pc.lookup(np.asarray(prompt_ids, np.int32), plan, peek=True,
                        salt=(b"" if not self.lora
                              else adapters_mod.prefix_salt(adapter)))
        if hit is None:
            return 0.0
        return min(1.0, hit[0].tokens / len(prompt_ids))

    # ------------------------------------------------ priority preemption

    def _victim_slot_admits(self, head: _Tracked, victim: _Tracked) -> bool:
        """Would preempting ``victim`` actually let ``head`` admit?  A
        swap-out that can't be followed by the head's admission is pure
        loss — the victim's stream stalls behind a still-stuck head
        (preemption frees a SLOT, never pages: the victim's KV refs
        ride along).  Checks the COLD page reservation (conservative: a
        same-shard cache hit could get by with fewer fresh pages, but
        cold is the guaranteed fallback route), and a preempted head's
        snapshot pins it to its own data shard."""
        if not self.hybrid:
            return True
        shard = self._slot_shard(victim.slot)
        if head.snapshot is not None:
            if head.snapshot.get("migrated"):
                # a migrated-in head brings page CONTENTS, not refs: it
                # re-allocates its full reservation in the freed slot's
                # shard, so that shard's free pages must cover it
                r = head.request
                return attention_page_count(
                    self.cfg, len(r.prompt_ids) + r.max_new_tokens
                ) <= self.page_pool.free_pages_in(shard)
            return shard == head.snapshot.get("shard", shard)
        r = head.request
        n_pages = attention_page_count(
            self.cfg, len(r.prompt_ids) + r.max_new_tokens
        )
        return n_pages <= self.page_pool.free_pages_in(shard)

    def _pick_victim(self):
        """The decoding slot to preempt for the queue's best request:
        lowest priority strictly below the incoming one, restricted to
        victims whose freed slot the head could actually occupy
        (``_victim_slot_admits``); ties prefer the fewest generated
        tokens (least latency already sunk), then the newest request.
        None when nothing is outranked — with uniform priorities
        preemption never triggers."""
        head = self.scheduler.peek()
        if head is None:
            return None
        victims = [t for t in self._slots.values()
                   if t.status is RequestStatus.DECODE
                   and t.priority < head.priority
                   and self._victim_slot_admits(head, t)]
        if not victims:
            return None
        return min(victims, key=lambda t: (t.priority, len(t.new_tokens),
                                           -t.request_id))

    def _preempt(self, tracked: _Tracked) -> None:
        """Swap a decoding slot out to host RAM: copy its carry + last
        logits off-device (the one deliberate sync on this path — a
        swap-out IS a device->host move), keep its KV page refs riding
        on the tracker (hybrid: zero page churn, the pages stay shard-
        pinned for the resume), free the slot, requeue.  ``_resume``
        restores via ``state_cache.restore`` with the token counter
        intact, so the continued stream is bit-exactly the one the
        swap-out interrupted — no re-prefill, no replayed token."""
        slot = tracked.slot
        with self.tracer.span("serving_preempt", slot=slot,
                              request=tracked.request_id,
                              trace=tracked.trace_id):
            state = state_cache.read_state(self.pool, slot)
            snap = {
                "blocks": jax.device_get(state["blocks"]),
                "logits": jax.device_get(self.pool["logits"][slot][None]),
                # device step counter, relative to the CURRENT request
                # (a hot-swapped continuation restarted it at 0 —
                # swap_base re-bases the emitted-token count)
                "step": len(tracked.new_tokens) - tracked.swap_base,
            }
            if self.hybrid:
                snap["kv_len"] = int(self._kv_len[slot])
                snap["shard"] = self._slot_shard(slot)
                self._page_tbl[slot] = 0
                self._kv_len[slot] = 0
            tracked.snapshot = snap
            tracked.preempted += 1
            self._preemptions += 1
            self.metrics.record_preemption()
            self.pool = state_cache.evict(self.pool, slot)
            del self._slots[slot]
            self._free.append(slot)
            self._free.sort()
            self.scheduler.requeue(tracked)

    def _pressure_evict(self, victim: _Tracked) -> None:
        """Free the victim's slot for the queue's best request: PREEMPT
        (carry to host RAM, KV page refs kept — the status quo), or —
        with a session store attached — PARK: the full replica-unbound
        artifact (KV page CONTENTS included) goes to the tiered store,
        the victim's pages recycle immediately, and its requeued
        tracker holds only a tiny session pointer.  Parking is the
        generalized valve: a pressure victim costs zero device pages
        and near-zero host RAM while it waits, instead of pinning a
        snapshot in RAM forever."""
        if self.session_store is None:
            self._preempt(victim)
        else:
            self._park_victim(victim)

    def _park_victim(self, tracked: _Tracked) -> None:
        """Pressure-driven park of a decoding slot: package the full
        migration-format artifact, store it, release slot + pages +
        adapter ref, and requeue the tracker with a session-pointer
        snapshot (``{"migrated", "parked", "session"}``) that
        ``_resume`` hydrates from the store only once a slot is
        actually available.  ``pop_preempted`` skips the pointer (it
        is ``migrated``-flagged — the resume needs a full page
        re-allocation, so it competes through normal admission)."""
        slot = tracked.slot
        with self.tracer.span("serving_park", slot=slot,
                              request=tracked.request_id,
                              trace=tracked.trace_id, pressure=True):
            snap = self._package_migration(slot, tracked)
            snap["parked"] = True
            # no TTL: the queued tracker owns this session's lifetime
            sid = self.session_store.park(
                {"request": None, "snapshot": snap}, ttl_s=0)
            self.pool = state_cache.evict(self.pool, slot)
            self._release_pages(slot, tracked)
            self._release_adapter_ref(tracked)
            del self._slots[slot]
            self._free.append(slot)
            self._free.sort()
            tracked.snapshot = {"migrated": True, "parked": True,
                                "session": sid}
            tracked.preempted += 1
            self._preemptions += 1
            self.metrics.record_preemption()
            self._session_parks += 1
            self.metrics.record_session_park()
            self.scheduler.requeue(tracked)

    def park(self, request_id: int) -> tuple[GenerationRequest, dict]:
        """Explicitly park a DECODING stream (client idled, or
        ``POST /v1/park``): serialize it into the replica-unbound park
        artifact — the migration artifact plus the tokens already
        emitted — release its slot, KV pages and adapter ref, and DROP
        it from this engine.  Returns ``(request, artifact)``; the
        caller persists the pair (a ``SessionStore``, or the
        controller's over the park RPC) and later resumes it through
        ``submit_migrated`` on ANY replica — the artifact carries page
        contents, never physical ids, so the resumed stream is
        bit-identical to one that never parked.  Raises ``ValueError``
        (retriable) for a stream not in a parkable state: queued or
        mid-prefill streams have no decode carry yet, and a stream
        with in-flight speculative drafts parks on the next tick, once
        the verify launch drains them."""
        tracked = next((t for t in self._slots.values()
                        if t.request_id == request_id), None)
        if tracked is None or tracked.status is not RequestStatus.DECODE:
            raise ValueError(
                f"request {request_id} is not parkable: only a resident "
                f"DECODING stream has the carry the park artifact "
                f"serializes (queued/prefilling streams finish prefill "
                f"first; retry shortly)"
            )
        if self.spec and tracked.spec_pending:
            raise ValueError(
                f"request {request_id} has {len(tracked.spec_pending)} "
                f"speculative draft token(s) in flight; retry after the "
                f"next verify tick drains them"
            )
        slot = tracked.slot
        with self.tracer.span("serving_park", slot=slot,
                              request=tracked.request_id,
                              trace=tracked.trace_id):
            snap = self._package_migration(slot, tracked)
            snap["parked"] = True
            snap["new_tokens"] = [int(t) for t in tracked.new_tokens]
            self.pool = state_cache.evict(self.pool, slot)
            self._release_pages(slot, tracked)
            self._release_adapter_ref(tracked)
            del self._slots[slot]
            self._free.append(slot)
            self._free.sort()
            if self.spec:
                self.drafter.forget(tracked.request_id)
            if self.session_store is not None:
                self._session_parks += 1
                self.metrics.record_session_park()
        return tracked.request, snap

    def hot_swap_adapter(self, request_id: int,
                         adapter: str | None = None) -> str:
        """Switch a live DECODING stream to another adapter version
        mid-flight — the PR-15 residual online tuning needs: when a
        tenant's ``name@v(N+1)`` deploys, an opted-in stream moves to
        it WITHOUT losing a token.  ``adapter`` pins the target
        (default: the latest version of the stream's current base).

        The recurrent carry was shaped by the OLD factors, so it is
        invalidated — exactly once — by evicting the slot and releasing
        its KV pages + adapter ref; the stream is then requeued as a
        CONTINUATION request whose prompt is the original prompt plus
        every token already emitted, decoding under the new version.
        ``tracked.new_tokens`` (and thus TokenEvent indices, SSE
        replay, and the finish record's token count) continue across
        the swap; ``tracked.orig_request`` preserves what the USER
        submitted for the finish record, and ``tracked.swap_base``
        re-bases the device step counter the continuation restarts
        (preempt/park/migration stamps subtract it).

        Returns the adapter name now in effect (a no-op when already
        there).  Raises retriable ``ValueError`` for streams not in a
        swappable state — queued/prefilling streams have no carry to
        invalidate yet, and in-flight speculative drafts drain on the
        next verify tick first (the ``park`` preconditions)."""
        if not self.lora:
            raise ValueError(
                "hot_swap_adapter needs multi-tenant LoRA serving "
                "(cfg.lora_max_adapters > 0)"
            )
        tracked = next((t for t in self._slots.values()
                        if t.request_id == request_id), None)
        if tracked is None or tracked.status is not RequestStatus.DECODE:
            raise ValueError(
                f"request {request_id} is not swappable: only a "
                f"resident DECODING stream holds the carry a swap "
                f"invalidates (queued/prefilling streams finish "
                f"prefill first; retry shortly)"
            )
        if self.spec and tracked.spec_pending:
            raise ValueError(
                f"request {request_id} has {len(tracked.spec_pending)} "
                f"speculative draft token(s) in flight; retry after "
                f"the next verify tick drains them"
            )
        r = tracked.request
        old = getattr(r, "adapter", None)
        if not old:
            raise ValueError(
                f"request {request_id} decodes the base model — there "
                f"is no adapter to swap"
            )
        new = self.adapters.resolve(
            adapter if adapter is not None else self.adapters.latest(old)
        )
        self.adapters.factors(new)  # UnknownAdapterError before any state change
        if new == old:
            return old
        slot = tracked.slot
        emitted = len(tracked.new_tokens)
        with self.tracer.span("serving_hot_swap", slot=slot,
                              request=tracked.request_id,
                              trace=tracked.trace_id,
                              adapter=new):
            # THE carry invalidation, exactly once: the old-factor
            # state, its KV pages and the old version's factor ref all
            # go — the release keys off tracked.request.adapter, so it
            # runs BEFORE the request mutates to the new version
            self.pool = state_cache.evict(self.pool, slot)
            self._release_pages(slot, tracked)
            self._release_adapter_ref(tracked)
            del self._slots[slot]
            self._free.append(slot)
            self._free.sort()
            if self.spec:
                # the drafter's observed history pairs with the old
                # stream; the continuation reseeds from its re-prefill
                self.drafter.forget(tracked.request_id)
            if tracked.orig_request is None:
                tracked.orig_request = r
            tracked.request = dataclasses.replace(
                r,
                prompt_ids=np.concatenate([
                    np.asarray(r.prompt_ids, np.int32),
                    np.asarray(tracked.new_tokens[tracked.swap_base:],
                               np.int32),
                ]),
                max_new_tokens=(r.max_new_tokens
                                - (emitted - tracked.swap_base)),
                adapter=new,
            )
            tracked.swap_base = emitted
            tracked.hot_swaps += 1
            self._hot_swaps += 1
            self.metrics.record_hot_swap()
            # requeue re-admits through the normal path: the
            # continuation re-prefills (prefix-warm under the NEW
            # version's salt where possible) and decodes on
            self.scheduler.requeue(tracked)
        return new

    def _resume(self, tracked: _Tracked) -> bool:
        """Re-admit a request from a host snapshot with ``step``
        preserved: a PREEMPTED request back into a free slot — the
        same data shard for hybrids, where its page refs live — or a
        MIGRATED one (``snapshot["migrated"]``, the prefill-tier
        handoff artifact) into any slot whose shard can cover its full
        page reservation: the pages are allocated HERE and the
        serialized KV contents scattered in (``state_cache
        .write_pages``), so the artifact is shard- and replica-
        agnostic.  Returns False (requeued) when no compatible slot is
        free yet."""
        snap = tracked.snapshot
        migrated = bool(snap.get("migrated"))
        # the adapter factor slot first (a preempted request's ref rode
        # its snapshot — instant; a MIGRATED one re-pins from THIS
        # engine's cache, waiting like any admission when all slots
        # are pinned)
        if not self._acquire_adapter_ref(tracked):
            self.scheduler.requeue(tracked)
            return False
        n_pages = 0
        if self.hybrid:
            if migrated:
                r = tracked.request
                n_pages = attention_page_count(
                    self.cfg, len(r.prompt_ids) + r.max_new_tokens
                )
                slot = next(
                    (s for s in self._free
                     if n_pages <= self.page_pool.free_pages_in(
                         self._slot_shard(s))), None)
            else:
                slot = next((s for s in self._free
                             if self._slot_shard(s) == snap["shard"]), None)
        else:
            slot = self._free[0] if self._free else None
        if slot is None:
            self.scheduler.requeue(tracked)
            return False
        self._free.remove(slot)
        t0 = time.perf_counter()
        if "session" in snap:
            # pressure-parked: hydrate the full artifact from the
            # tiered store only now that a slot is actually free (an
            # eager hydrate on a tracker that then failed admission
            # would haul the artifact back into host RAM for nothing)
            try:
                snap = self.session_store.resume(snap["session"])["snapshot"]
                tracked.snapshot = snap
            except (KeyError, SessionStoreError):
                # the parked artifact is gone (store restarted without
                # its state dir, or the frame failed its CRC): this
                # stream cannot continue — drop it finished-with-error
                # instead of crashing the admission loop (the
                # named-error/skip contract), its emitted tokens still
                # replayable from the recent-finished ring
                self._release_adapter_ref(tracked)
                self._free.insert(0, slot)
                self._free.sort()
                tracked.snapshot = None
                self._recent_finished[tracked.request_id] = (
                    list(tracked.new_tokens), "session_lost")
                while (len(self._recent_finished)
                       > self.RECENT_FINISHED_KEEP):
                    self._recent_finished.pop(
                        next(iter(self._recent_finished)))
                return True
        parked = bool(snap.get("parked"))
        r = tracked.request
        try:
            with self.tracer.span("serving_resume", slot=slot,
                                  request=tracked.request_id,
                                  trace=tracked.trace_id,
                                  **({"migrated": True} if migrated
                                     else {})):
                if self.hybrid and migrated:
                    tracked.pages = self.page_pool.alloc(
                        n_pages, self._slot_shard(slot)
                    )
                    self._page_allocs += n_pages
                    n_live = snap["n_live"]
                    if n_live:
                        # dst ids padded to the artifact's pow2 page
                        # bucket with the trash page (whose contents
                        # are garbage by contract), so one scatter
                        # trace covers every page count
                        bucket = jax.tree.leaves(
                            snap["kv_data"])[0].shape[1]
                        dst = np.zeros((bucket,), np.int32)
                        dst[:n_live] = tracked.pages[:n_live]
                        self.pool["state"]["attn_blocks"] = \
                            state_cache.write_pages(
                                self.pool["state"]["attn_blocks"],
                                jax.tree.map(jnp.asarray,
                                             snap["kv_data"]),
                                jnp.asarray(dst),
                            )
                self.pool = state_cache.restore(
                    self.pool, slot,
                    {"blocks": jax.tree.map(jnp.asarray, snap["blocks"])},
                    jnp.asarray(snap["logits"]), r.resolve_key(),
                    snap["step"], r.max_new_tokens, r.top_k,
                    r.temperature, -1 if r.eos_id is None else r.eos_id,
                    adapter_id=tracked.adapter_slot or 0,
                )
                if self.hybrid:
                    self._page_tbl[slot] = 0
                    self._page_tbl[slot, :len(tracked.pages)] = tracked.pages
                    self._kv_len[slot] = snap["kv_len"]
        except Exception:
            # slot back, request back — the snapshot survives requeue,
            # so a retry restores instead of re-prefilling (a re-prefill
            # would replay tokens the consumer already has).  Pages a
            # MIGRATED restore allocated here are returned (its data
            # lives on in the snapshot; a retry re-allocates).
            if migrated and tracked.pages:
                self.page_pool.free(tracked.pages)
                self._page_frees += len(tracked.pages)
                tracked.pages = None
                self._page_tbl[slot] = 0
                self._kv_len[slot] = 0
            self._free.insert(0, slot)
            self._free.sort()
            self.scheduler.requeue(tracked)
            raise
        if self.spec and not tracked.spec_pending:
            # a MIGRATED-in request arrives with a fresh tracker: derive
            # its first pending token from the artifact's logits — the
            # same bits the source engine's seed would have used, so the
            # resumed stream matches a never-migrated one exactly.  A
            # locally-preempted request keeps its surviving pending.
            self._seed_spec(tracked, snap["logits"])
        tracked.snapshot = None
        tracked.slot = slot
        tracked.status = RequestStatus.DECODE
        self._slots[slot] = tracked
        if migrated and tracked.itl_hist is None:
            # a migrated-in tracker is FRESH on this scheduler and
            # skipped _admit's lifecycle stamping: the admission stamp
            # travels in the artifact (queue-wait was recorded once,
            # on the prefill replica — re-recording here would double-
            # count it in the histogram) and the per-request ITL
            # histogram starts empty (no token has streamed yet)
            tracked.t_admit = snap.get("t_admit") or time.perf_counter()
            tracked.itl_hist = StreamingHistogram()
        if migrated and not parked:
            # handoff latency = source-side packaging + this restore's
            # host dispatch (the router's serving_migrate span covers
            # the placement hop between them)
            dt_ms = (snap.get("package_ms", 0.0)
                     + (time.perf_counter() - t0) * 1000)
            tracked.migrations += 1
            tracked.migration_ms += dt_ms
            self._migrations_in += 1
            self.metrics.record_migration_in(dt_ms)
        elif parked and self.session_store is not None:
            # a parked resume is NOT a tier migration (the counters
            # stay clean); it lands in the sessions resume-latency
            # histogram instead — store hydrate + restore dispatch
            self._session_resumes += 1
            self.metrics.record_session_resume(
                (time.perf_counter() - t0) * 1000)
        return True

    # ------------------------------------- disaggregated tier migration

    def _package_migration(self, slot: int, tracked: _Tracked) -> dict:
        """Serialize a prefill-complete slot into the migration
        artifact: the same preempt-style host snapshot
        ``state_cache.restore`` consumes (O(1) conv/SSM carry + last
        logits + the token counter, here 0) plus — hybrids — the live
        KV pages' contents read out of the page pool
        (``state_cache.read_pages``, pow2-bucketed page count so one
        gather trace covers every prompt length).  The ``device_get``
        is the one deliberate sync on this path: a migration IS a
        device->host->device move, and Mamba makes it O(1) in the
        sequence length (plus O(prompt) KV pages only for hybrid
        stacks)."""
        t0 = time.perf_counter()
        state = state_cache.read_state(self.pool, slot)
        snap = {
            "migrated": True,
            "blocks": jax.device_get(state["blocks"]),
            "logits": jax.device_get(self.pool["logits"][slot][None]),
            # relative to the CURRENT request: a hot-swapped stream's
            # continuation restarted the device counter at 0, and the
            # receiver restores against the continuation's budget
            "step": len(tracked.new_tokens) - tracked.swap_base,
            # only swapped streams stamp the re-base (artifacts from
            # never-swapped streams stay byte-identical to PR-19's)
            **({"swap_base": tracked.swap_base}
               if tracked.swap_base else {}),
            "t_submit": tracked.t_submit,
            "t_admit": tracked.t_admit,
            # clock-transportable journey stamps: raw perf_counter
            # values are meaningless on another HOST (each machine has
            # its own monotonic epoch), so the artifact also carries
            # AGES at packaging time — the receiver reconstructs
            # equivalent local stamps, keeping queue-wait/TTFT/e2e
            # correct across genuine host boundaries (the wire transit
            # itself lands in the journey, as it should)
            "t_submit_age_s": t0 - tracked.t_submit,
            "t_admit_age_s": (None if tracked.t_admit is None
                              else t0 - tracked.t_admit),
        }
        if self.hybrid:
            kv_len = int(self._kv_len[slot])
            n_live = -(-kv_len // self.cfg.kv_page_tokens) if kv_len else 0
            bucket = next_pow2_bucket(max(n_live, 1), min_bucket=1)
            ids = np.zeros((bucket,), np.int32)  # pad -> trash page 0
            ids[:n_live] = tracked.pages[:n_live]
            snap["kv_data"] = jax.device_get(state_cache.read_pages(
                self.pool["state"]["attn_blocks"], jnp.asarray(ids)
            ))
            snap["kv_len"] = kv_len
            snap["n_live"] = n_live
        snap["package_ms"] = (time.perf_counter() - t0) * 1000
        return snap

    def _migrate_ready(self) -> None:
        """Prefill-tier handoff (``migrate_hook`` engines only): offer
        every prefill-complete slot — DECODE status, zero tokens
        emitted, so chunked, one-shot and full-cache-hit prefills all
        qualify — to the hook BEFORE it ever decodes here.  The hook
        (serving/router._migrate_from) re-places the packaged artifact
        on a decode-tier replica and returns True: this engine then
        frees the slot and drops its page refs (the artifact carries
        page CONTENTS, so the physical pages recycle immediately).
        False = no decode capacity right now: the slot decodes HERE
        (mixed-mode fallback) and is marked ``no_migrate`` so it is
        offered exactly once — graceful degradation, never a stall."""
        for slot in [s for s, t in self._slots.items()
                     if t.status is RequestStatus.DECODE
                     and not t.new_tokens and not t.no_migrate]:
            tracked = self._slots[slot]
            if self.migrate_hook(
                tracked,
                lambda s=slot, t=tracked: self._package_migration(s, t),
            ):
                self.pool = state_cache.evict(self.pool, slot)
                self._release_pages(slot, tracked)
                self._release_adapter_ref(tracked)
                del self._slots[slot]
                self._free.append(slot)
                self._free.sort()
                if self.spec:
                    # the target engine reseeds from the artifact's
                    # logits and restarts its own drafter stream
                    self.drafter.forget(tracked.request_id)
                self._migrations_out += 1
                self.metrics.record_migration_out()
            else:
                tracked.no_migrate = True

    # chunk grants a slot can be passed over in a row before it outranks
    # SRPT's shortest-remaining rule (the starvation guard)
    SRPT_STARVATION_GRANTS = 4

    def _pick_prefill_slot(self) -> int:
        """Which in-flight partial prefill gets the next chunk grant.

        ``cfg.prefill_schedule == "rr"`` takes the rotation head —
        ``_advance_prefill`` moves a still-partial slot to the back, so
        repeatedly granting the head IS the round-robin PR 4 pinned.
        ``"srpt"`` grants the slot with the fewest REMAINING chunks
        (shortest-remaining-processing-time: a nearly-done prompt
        reaches its first token before a fresh long one begins, which
        minimizes mean TTFT across concurrent prefills), except that a
        slot passed over ``SRPT_STARVATION_GRANTS`` times in a row gets
        the grant regardless — a stream of short arrivals can't starve
        a long prompt indefinitely.  Ties break toward the prefill
        queue head (rotation order: a granted-but-partial slot moves to
        the back, so among tied slots the one granted least recently
        wins)."""
        queue = self._prefill_queue
        if self.cfg.prefill_schedule != "srpt" or len(queue) == 1:
            self._slots[queue[0]].prefill_skipped = 0  # a grant is a grant
            return queue[0]
        starved = [s for s in queue
                   if (self._slots[s].prefill_skipped
                       >= self.SRPT_STARVATION_GRANTS)]
        if starved:
            pick = starved[0]
        else:
            pick = min(queue, key=lambda s: (
                self._slots[s].plan.n_chunks - self._slots[s].chunks_done
            ))
        for s in queue:
            if s != pick:
                self._slots[s].prefill_skipped += 1
        self._slots[pick].prefill_skipped = 0
        return pick

    def _prefill_phase(self) -> tuple[float, int]:
        """Between-ticks prefill work: admit what fits, then spend the
        chunk budget one grant at a time across in-flight partial
        prefills — ``_pick_prefill_slot`` chooses each grant (rotation
        under ``cfg.prefill_schedule="rr"``, shortest-remaining-first
        with a starvation guard under ``"srpt"``) — so a second long
        prompt makes progress instead of waiting for the first to
        drain (FCFS head-of-line blocking on TTFT).  At least one
        chunk runs per step even when the budget is smaller than a
        chunk, so progress is guaranteed.

        Priority pressure valve: when the queue's best request outranks
        a resident decoding slot and no slot is free, the lowest-
        priority victim is PREEMPTED (carry swapped to host, slot
        freed, resumed later without re-prefill) so the high-priority
        request admits this step instead of queueing behind it.
        Returns (host seconds spent — the tick's ``prefill_stall`` —
        and chunk tokens dispatched)."""
        # one victim scan serves both the gate and the loop's first
        # iteration (peek + slot scan per engine step adds up)
        next_victim = (self._pick_victim()
                       if self.scheduler.depth and not self._free else None)
        if not ((self._free and self.scheduler.depth) or self._prefill_queue
                or next_victim is not None):
            return 0.0, 0
        t0 = time.perf_counter()
        chunk_tokens0 = self.metrics.prefill_chunk_tokens
        chunk_s0 = self.metrics.prefill_chunk_time_s
        if self.scheduler.depth and (self._free or next_victim is not None):
            with self.tracer.span("serving_admit",
                                  queued=self.scheduler.depth):
                while self.scheduler.depth:
                    if not self._free:
                        victim = next_victim or self._pick_victim()
                        next_victim = None
                        if victim is None:
                            break
                        self._pressure_evict(victim)
                    if not self._admit(self.scheduler.pop()):
                        # the head stalled on KV pages or a shard-pinned
                        # slot.  A suitable victim may still unblock it
                        # — a free slot in the WRONG shard suppressed
                        # the gate above (_victim_slot_admits guarantees
                        # the retry admits) — else resume parked
                        # preempted requests: their swap-ins need no
                        # pages and eventually release the pages the
                        # head is waiting on.
                        victim = self._pick_victim()
                        if victim is not None:
                            self._pressure_evict(victim)
                            continue
                        self._resume_parked()
                        break
        budget = self.prefill_tokens_per_tick
        left = float("inf") if budget == 0 else float(budget)
        if self.spec and budget:
            # verify ticks consume token lanes of interleaving budget
            # too (the previous tick computed live * (K+1) chunk-width
            # lanes): debit them so speculation on + chunked prefill
            # never exceeds the per-step work bound the knob promises.
            # The >=1-chunk progress guarantee below still holds.
            left = max(0.0, left - self._spec_budget_debt)
            self._spec_budget_debt = 0
        chunks_run = 0
        while self._prefill_queue and (left > 0 or chunks_run == 0):
            left = self._advance_prefill(self._pick_prefill_slot(), left)
            chunks_run += 1
        self._pending_chunk_ms += (
            self.metrics.prefill_chunk_time_s - chunk_s0
        ) * 1000
        return (time.perf_counter() - t0,
                self.metrics.prefill_chunk_tokens - chunk_tokens0)

    # ------------------------------------------------------------- decoding

    @property
    def pending(self) -> int:
        """Requests not yet finished (queued + in-flight)."""
        return self.scheduler.depth + len(self._slots)

    # ------------------------------------------------- the lane ladder

    def _tick_width(self, live_slots) -> int:
        """Lane width of this tick's launch: the narrowest rung of the
        ladder that holds the BUSIEST data shard's live slots — every
        shard gets the same lane count, so the lanes tile over the data
        axis exactly like the full pool — taken wider at once, narrower
        only after ``RUNG_HYSTERESIS_TICKS`` consecutive ticks that would
        have fitted the narrower rung.  The last rung is the capacity."""
        by_shard = [0] * self.num_shards
        for s in live_slots:
            by_shard[self._slot_shard(s)] += 1
        lanes = max(by_shard) * self.num_shards
        need = next(i for i, w in enumerate(self._rungs) if w >= lanes)
        if need > self._rung:
            self._rung = need
            self._shrink_streak = 0
        elif need < self._rung:
            self._shrink_streak += 1
            if self._shrink_streak >= RUNG_HYSTERESIS_TICKS:
                self._rung = need
                self._shrink_streak = 0
        else:
            self._shrink_streak = 0
        return self._rungs[self._rung]

    def _lane_maps(self, live_slots, width: int):
        """Host-side lane maps for one narrow launch: ``idx`` (W,) gathers
        lane j from slot idx[j], ``keep`` (W,) marks the lanes that carry
        a live slot (a pad lane repeats its shard's first slot, runs
        inactive and is dropped by the write-back), and ``lanes`` maps
        slot -> lane for the host-side token plumbing.  Shard d's live
        slots land in lanes [d*b, d*b + n_d): the gather is shard-local,
        so the mesh-sharded pool's tiling survives."""
        b = width // self.num_shards
        per = self.capacity // self.num_shards
        idx = np.repeat(np.arange(self.num_shards, dtype=np.int32) * per, b)
        keep = np.zeros((width,), bool)
        lanes: dict[int, int] = {}
        fill = [d * b for d in range(self.num_shards)]
        for s in sorted(live_slots):
            d = self._slot_shard(s)
            lane = fill[d]
            fill[d] += 1
            idx[lane] = s
            keep[lane] = True
            lanes[s] = lane
        return idx, keep, lanes

    def _page_bucket(self, slots, spare: bool = False) -> int:
        """Page-count BUCKET of a hybrid launch over ``slots``: the pow2
        of their largest allocation (+1 spare trash column in spec mode),
        so attention reads scale with what the launch's lanes hold (one
        trace per bucket and rung; bucket width changes never perturb
        token streams — masked attention is bit-stable across page-bucket
        widths, models/attention.py)."""
        largest = max(
            (len(self._slots[s].pages) for s in slots
             if self._slots[s].pages),
            default=1,
        )
        return min(
            next_pow2_bucket(largest + (1 if spare else 0), min_bucket=1),
            self._page_tbl.shape[1],
        )

    def _lane_page_meta(self, idx, keep, bucket: int):
        """Page table + lengths of a narrow hybrid launch: the live slots'
        rows in lane order, pad lanes pointing at the trash page with
        length 0."""
        ctbl = self._page_tbl[idx, :bucket].copy()
        clen = self._kv_len[idx].copy()
        ctbl[~keep] = 0
        clen[~keep] = 0
        return ctbl, clen

    def _scatter_pool(self, new_state, lanes_out, idx, keep):
        """Reassemble ``self.pool`` from a narrow SPECULATIVE launch's
        output: write the lanes back into the donated full-width rows and
        carry the page pool forward from the launch's own donation."""
        res = state_cache.scatter_slots(
            _slot_rows(self.pool), lanes_out,
            jnp.asarray(idx), jnp.asarray(keep), mesh=self.mesh,
        )
        self.pool = _with_slot_rows({"state": new_state}, res)

    def _pipeline_micro(self, width: int) -> int | None:
        """Microbatch count for the explicit GPipe decode schedule, or
        None for the GSPMD layer scan.

        The explicit clock (parallel/pipeline.pipelined_decode_layers)
        engages only where it is defined and profitable: a 3-D mesh
        with ``stage > 1`` whose other axes are size 1, a pure-SSM
        stack (hybrid attention needs the paged-KV metadata plumbing
        the schedule doesn't thread), no multi-tenant LoRA (bound
        factor pools carry a per-slot axis the schedule doesn't
        slice), and the non-speculative tick (spec_verify launches are
        chunk-shaped, not lane-shaped).  Everywhere else the
        stage-sharded layer axis still partitions residency and GSPMD
        executes the sequential scan — the bitwise-identical fallback.

        ``n_micro = stage_shards`` when the launch width tiles over
        the stages (the ladder's doubling rungs make this the common
        case), else 1 (a sequential flush — still one trace per
        rung, so TRACE_COUNTS stay flat across repeated ticks)."""
        if (self.stage_shards <= 1 or self.hybrid or self.spec
                or self.lora or self.model_shards > 1
                or self.num_shards > 1):
            return None
        return (self.stage_shards if width % self.stage_shards == 0
                else 1)

    def _run_tick(self, live_slots, width: int, bucket=None, span=None):
        """One decode tick at rung ``width``: a single ``_tick`` launch.
        Under the capacity the launch is NARROW — the live slots' rows
        gathered into ``width`` lanes, advanced, and written back into
        the donated pool, all inside the one program; pad lanes run
        inactive, their hybrid KV writes land on the trash page (their
        table rows are zeroed) and nothing reads them back — and the
        token matrices are expanded to slot indexing for the shared
        event plumbing.  At the capacity it is the whole pool, as it
        stands.  Per-row math is the same at every rung, so streams are
        bit-identical whatever the ladder (tests/test_tick_compaction.py).

        With no ``live_slots`` the launch is the ladder's warm-up: every
        lane pad (at the capacity: every slot parked by ``idle_meta``),
        nothing advanced, nothing written, nothing fetched.

        ``span`` is the launch's ``serving_tick`` span: a model with expert
        layers sets the launch's counters on it (``_note_expert_load``)."""
        narrow = width < self.capacity
        warm = not live_slots
        pool, tick_kv, lanes_dev, parked = self.pool, (), None, None
        if narrow:
            idx, keep, lanes = self._lane_maps(live_slots, width)
            lanes_dev = (jnp.asarray(idx), jnp.asarray(keep))
            if self.hybrid:
                tick_kv = self._lane_page_meta(idx, keep, bucket)
        else:
            if self.hybrid:
                tick_kv = (self._page_tbl[:, :bucket], self._kv_len)
            if warm:
                parked = pool["meta"]  # kept out of the launch's donation
                pool = {**pool, "meta": state_cache.idle_meta(parked)}
        pool, tokens, emitted, done, load = _tick(
            self._params, pool, *map(jnp.asarray, tick_kv),
            lanes=lanes_dev, cfg=self.cfg, k_max=self.max_top_k,
            steps=self.tokens_per_tick, mesh=self.mesh,
            n_micro=self._pipeline_micro(width),
        )
        if parked is not None:
            pool = {**pool, "meta": parked}
        self.pool = pool
        if warm:
            return None
        tokens = np.asarray(tokens)  # (steps, width) — the host sync
        emitted = np.asarray(emitted)
        done = np.asarray(done)
        if load is not None:
            # the launch has returned: its load, and that of the chunks
            # dispatched before it, are there to be read
            self._note_expert_load(span, load, int(emitted.sum()))
        if narrow:
            steps = tokens.shape[0]
            cols = np.fromiter(lanes.keys(), np.int64, len(lanes))
            ls = np.fromiter(lanes.values(), np.int64, len(lanes))
            tokens_f = np.zeros((steps, self.capacity), tokens.dtype)
            emitted_f = np.zeros((steps, self.capacity), bool)
            done_f = np.zeros((steps, self.capacity), bool)
            tokens_f[:, cols] = tokens[:, ls]
            emitted_f[:, cols] = emitted[:, ls]
            done_f[:, cols] = done[:, ls]
            tokens, emitted, done = tokens_f, emitted_f, done_f
        if self.hybrid:
            # mirror the device-side lengths advance: +1 per live
            # sub-step, exactly what `emitted` marks
            self._kv_len += emitted.sum(axis=0).astype(np.int32)
        return tokens, emitted, done

    def _note_expert_load(self, span, load, rows: int) -> None:
        """A launch's expert load, once its fetch has returned: counted in
        ``self.metrics`` and set on the launch's span as ``expert_rows``
        (with ``expert_hits``), ``expert_rows_share`` and
        ``expert_load_max_over_mean``.  ``rows``
        is what the launch served (live lane sub-steps, real chunk tokens);
        each offered ``moe_top_k`` choices in each layer.  The chunks
        dispatched before this launch ran before it: theirs are read here
        too, with no fetch of their own."""
        offered = self.cfg.moe_top_k * self.cfg.n_layer
        for sp, ld, n in (*self._chunk_loads, (span, load, rows)):
            stats = self.metrics.record_expert_load(
                np.asarray(ld), n * offered)
            if getattr(sp, "attrs", None) is not None:
                sp.attrs.update(stats)
        self._chunk_loads.clear()

    def _warm_ladder(self, width: int, bucket) -> None:
        """A launch shape the engine has not run brings its whole ladder
        with it: before the first tick (hybrid: the first at a new
        page-count bucket) every OTHER rung is run once at that shape,
        through the real launch path, with nothing live.  A burst that
        later needs a wider rung then finds its program compiled (or
        loaded from the cache) — no compilation in the middle of
        serving, whatever the traffic before it reached."""
        if bucket in self._warm_shapes:
            return
        self._warm_shapes.add(bucket)
        for w in self._rungs:
            if w != width:
                self._run_tick((), w, bucket)

    def _spec_tick(self, width: int):
        """One speculative draft-verify tick (serving/spec_decode.py).

        ``width`` (from ``_tick_width``) under the capacity narrows the
        launch to the live lanes: the feed/verify/commit all run at lane
        width and the committed lanes are written back — the same
        per-row math at a narrower batch, so the narrow spec stream is
        bit-identical to the full-width one (and to plain greedy).  The
        verify and the commit are programs of their own, so the gather
        and the write-back are too (``state_cache.gather_slots`` /
        ``scatter_slots``), and a rung is compiled when it is first used.

        Per live slot: compose the feed (its pending committed tokens +
        up to K drafter proposals, zero-filled to the static width W),
        run ONE ``spec_verify`` launch over the whole pool, fetch the
        (S, W) greedy matrix — the tick's one host sync — and decide
        per slot: a full verification commits the launch's carries and
        final logits outright (the state advanced W tokens) plus one
        bonus token from the final position's argmax; any rejection
        rolls the slot back to its pre-tick carries (``spec_commit``'s
        per-row select) and banks the accepted prefix + the model's
        correction token as the next tick's trusted feed — every launch
        commits >= 1 token per live slot.  Mid-prefill/empty/done slots
        are masked (their KV writes flush to trash, their garbage
        carries are discarded by the rollback select), exactly like the
        non-speculative tick's ``write_mask``.

        Returns ``(tokens, emitted, done)`` shaped (W+1, S) — the same
        matrices the compiled tick yields, so ``step()``'s event/
        latency/finish plumbing is shared verbatim."""
        W = self.spec_width
        S = self.capacity
        live = {s: t for s, t in self._slots.items()
                if t.status is RequestStatus.DECODE}
        compacted = width < S
        if compacted:
            idx, keep, lanes = self._lane_maps(list(live), width)
            n_lanes = width
        else:
            lanes = {s: s for s in live}
            n_lanes = S
        ids = np.zeros((n_lanes, W), np.int32)
        tmask = np.zeros((n_lanes, W), np.float32)
        trusted: dict[int, int] = {}
        for slot, tr in live.items():
            rid = tr.request_id
            if tr.spec_observed == 0:
                # fresh (or restarted-after-requeue) stream: drop any
                # stale drafter state before re-observing from scratch
                self.drafter.forget(rid)
            # committed history the drafter must know is prompt +
            # emitted + the still-unemitted pending (fresh tok0);
            # spec_observed counts how much of that concatenation the
            # drafter has seen, so only the SUFFIX is materialized —
            # never the whole history (O(new tokens) per tick, not
            # O(prompt + stream))
            pend = tr.spec_pending[tr.spec_pending_emitted:]
            plen = len(tr.request.prompt_ids)
            total = plen + len(tr.new_tokens) + len(pend)
            if total > tr.spec_observed:
                k = tr.spec_observed - plen
                if k < 0:
                    delta = (tr.request.prompt_ids[k:].tolist()
                             + tr.new_tokens + pend)
                elif k <= len(tr.new_tokens):
                    delta = tr.new_tokens[k:] + pend
                else:
                    delta = pend[k - len(tr.new_tokens):]
                self.drafter.observe(rid, delta)
                tr.spec_observed = total
            n = W - len(tr.spec_pending)
            drafts = (list(self.drafter.draft(rid, n))[:n] if n > 0
                      else [])
            self._spec_drafted += n
            ids[lanes[slot]] = spec_decode.build_feed(
                tr.spec_pending, drafts, W
            )
            tmask[lanes[slot]] = 1.0
            trusted[slot] = len(tr.spec_pending)
        if compacted:
            gathered = state_cache.gather_slots(
                _slot_rows(self.pool), jnp.asarray(idx),
                jnp.asarray(keep), mesh=self.mesh,
            )
            state_in = {"blocks": gathered["blocks"]}
            logits_in, meta_in = gathered["logits"], gathered["meta"]
            if self.hybrid:
                state_in["attn_blocks"] = \
                    self.pool["state"]["attn_blocks"]
                ctbl, clen = self._lane_page_meta(
                    idx, keep, self._page_bucket(lanes, spare=True))
                state_in["attn_meta"] = (jnp.asarray(ctbl),
                                         jnp.asarray(clen))
        else:
            state_in = dict(self.pool["state"])
            logits_in, meta_in = self.pool["logits"], self.pool["meta"]
            if self.hybrid:
                # +1 past the largest allocation so a fully-reserved
                # slot's overshoot writes clamp onto a zero (trash)
                # table entry — the table rows carry a permanent spare
                # column for exactly this (see __init__)
                bucket = self._page_bucket(self._slots, spare=True)
                state_in["attn_meta"] = (
                    jnp.asarray(self._page_tbl[:, :bucket]),
                    jnp.asarray(self._kv_len),
                )
        greedy_d, final_logits, new_state, old = spec_decode.spec_verify(
            self._params, state_in, jnp.asarray(ids), jnp.asarray(tmask),
            cfg=self.cfg, mesh=self._tp_mesh,
            **({"adapter_ids": meta_in["adapter_id"]} if self.lora
               else {}),
        )
        greedy = np.asarray(greedy_d)  # (lanes, W) — the host sync point
        tokens = np.zeros((W + 1, S), np.int32)
        emitted = np.zeros((W + 1, S), bool)
        done = np.zeros((W + 1, S), bool)
        advance = np.zeros((n_lanes,), bool)
        for slot, tr in live.items():
            nt = trusted[slot]
            fed = ids[lanes[slot]].tolist()
            a, adv, nxt = spec_decode.verify_greedy(
                fed, greedy[lanes[slot]], nt
            )
            self._spec_accepted += a
            pending = tr.spec_pending
            stream = (pending[tr.spec_pending_emitted:]
                      + fed[nt:nt + a] + [nxt])
            r = tr.request
            emitted_now: list[int] = []
            finished = False
            for tok in stream:
                emitted_now.append(tok)
                # the same finish rule the compiled tick applies: the
                # eos/budget token itself is emitted, nothing after it
                if r.eos_id is not None and tok == r.eos_id:
                    finished = True
                    break
                if (len(tr.new_tokens) + len(emitted_now)
                        >= r.max_new_tokens):
                    finished = True
                    break
            for j, tok in enumerate(emitted_now):
                tokens[j, slot] = tok
                emitted[j, slot] = True
            if finished:
                done[len(emitted_now) - 1, slot] = True
            elif adv:
                advance[lanes[slot]] = True
                tr.spec_pending = [nxt]
                tr.spec_pending_emitted = 1
            else:
                tr.spec_pending = pending + fed[nt:nt + a] + [nxt]
                tr.spec_pending_emitted = len(tr.spec_pending)
        # next step's chunk budget pays for this tick's verify lanes —
        # the lanes actually COMPUTED: the rung's width when the launch
        # was narrow, the live count otherwise
        self._spec_budget_debt = (width if compacted else len(live)) * W
        self._spec_streams += len(live)
        new_state = {k: v for k, v in new_state.items()
                     if k != "attn_meta"}
        committed = spec_decode.spec_commit(
            new_state, old["blocks"], logits_in, meta_in, final_logits,
            jnp.asarray(advance), jnp.int32(W),
        )
        if compacted:
            self._scatter_pool(committed["state"], _slot_rows(committed),
                               idx, keep)
        else:
            self.pool = committed
        if self.hybrid:
            # lengths advance by the full chunk width on accepted rows
            # only; rejected rows' freshly written cells stay dead-by-
            # lengths and the next verify overwrites them
            adv_full = np.zeros((S,), bool)
            for slot, lane in lanes.items():
                adv_full[slot] = advance[lane]
            self._kv_len += (W * adv_full).astype(np.int32)
        return tokens, emitted, done

    def step(self) -> list[TokenEvent]:
        """One engine iteration: prefill phase (admissions + chunk
        budget), then one compiled tick, streaming its tokens.

        Returns the tick's TokenEvents in emission order (empty while
        only partial prefills are resident); finished requests are
        evicted and their GenerationResults recorded in ``self.results``.
        """
        stall_s, chunk_tokens = self._prefill_phase()
        if stall_s:
            self.metrics.record_prefill_stall(stall_s)
        self._pending_stall_ms += stall_s * 1000
        self._pending_chunk_tokens += chunk_tokens
        if self.migrate_hook is not None:
            # prefill-tier handoff BEFORE the tick: a slot that just
            # finished prefill migrates out without decoding a single
            # token here (zero replayed tokens by construction)
            self._migrate_ready()
        if not any(t.status is RequestStatus.DECODE
                   for t in self._slots.values()):
            # nothing decodable yet (empty engine, or every resident slot
            # still mid-prefill): no tick this step — the loop keeps
            # granting chunk budget until a slot turns decodable
            return []
        occupied = len(self._slots)
        live_slots = [s for s, t in self._slots.items()
                      if t.status is RequestStatus.DECODE]
        # the rung this tick launches at: the lanes it computes.
        # Mid-prefill residents stay OUT of a narrow launch entirely —
        # their parked carries are simply never gathered — so the tick
        # is priced by decodable slots, not residency.
        width = self._tick_width(live_slots)
        # the explicit GPipe schedule's honest bubble bill (_pipeline_micro
        # documents the gate): the warmup/drain ramp idles
        # (stage_shards - 1) stage-ticks per lm_step call, worth
        # (stage_shards - 1) * microbatch_width full-depth lane
        # equivalents x tokens_per_tick sub-steps
        n_micro = self._pipeline_micro(width)
        bubble_lanes = 0
        if n_micro:
            bubble_lanes = ((self.stage_shards - 1) * (width // n_micro)
                            * self.tokens_per_tick)
        # live trace-id set: the requests this tick actually advances
        # (mid-prefill residents are masked out of sampling) — stamped
        # on the span AND the jsonl record so host-side attribution can
        # apportion tick_ms / analytic FLOPs across residents
        live_traces = sorted(
            t.trace_id for t in self._slots.values()
            if t.status is RequestStatus.DECODE
        )
        t0 = time.perf_counter()
        # ``occupied`` counts residents, ``live`` the slots this tick
        # decodes, ``width`` the lanes it launches (>= live);
        # ``prefill_tokens`` is what the prefill phase dispatched since
        # the last tick (chunk lanes plus one-shot prompt tokens)
        with self.tracer.span("serving_tick", occupied=occupied,
                              live=len(live_slots), width=width,
                              prefill_tokens=(
                                  self._pending_chunk_tokens
                                  + self._pending_oneshot_real_tokens),
                              traces=live_traces) as span:
            if self.spec:
                # speculative draft-verify tick: one lm_verify_chunk
                # launch commits up to spec_width+1 tokens per slot
                # (serving/spec_decode.py); _spec_tick owns the hybrid
                # lengths mirror (it advances by the chunk width only
                # on full accepts)
                tokens, emitted, done = self._spec_tick(width)
            else:
                bucket = (self._page_bucket(live_slots) if self.hybrid
                          else None)
                self._warm_ladder(width, bucket)
                tokens, emitted, done = self._run_tick(
                    live_slots, width, bucket, span)
        t_now = time.perf_counter()
        with self.tracer.span("serving_emit"):
            return self._emit(tokens, emitted, done, t0, t_now, occupied,
                              width, live_traces, bubble_lanes)

    def _emit(self, tokens, emitted, done, t0: float, t_now: float,
              occupied: int, width: int, live_traces, bubble_lanes: int
              ) -> list[TokenEvent]:
        """``step()``'s host work once the tick's arrays are on the host
        (span ``serving_emit``): token events, latency stamps, evictions,
        the finished requests' records, the tick's record."""
        dt = t_now - t0

        events: list[TokenEvent] = []
        for j in range(tokens.shape[0]):
            for slot, tracked in self._slots.items():
                if not emitted[j, slot]:
                    continue
                r = tracked.request
                tok = int(tokens[j, slot])
                tracked.new_tokens.append(tok)
                # the finish RULE lives in _tick; the host only reads its
                # verdict and labels the reason from the emitted token
                if done[j, slot]:
                    tracked.status = RequestStatus.FINISHED
                    tracked.finish_reason = (
                        "eos" if (r.eos_id is not None and tok == r.eos_id)
                        else "length"
                    )
                events.append(TokenEvent(
                    tracked.request_id, tok, len(tracked.new_tokens) - 1,
                    bool(done[j, slot]), tracked.finish_reason,
                ))
        # --- per-request latency stamps (must precede eviction).  Tokens
        # land on the host at the tick fetch, so a tick's m tokens share
        # one timestamp; the per-token ITL observation is the span since
        # the request's previous arrival (tick start for its first tick)
        # divided by m — the finest granularity the host can see.
        for slot, tracked in self._slots.items():
            m = int(emitted[:, slot].sum())
            if not m:
                continue
            if tracked.t_first_token is None:
                tracked.t_first_token = t_now
                self.metrics.record_ttft(t_now - tracked.t_submit)
                # TTFT split where it is spent; the three sum to it
                t_admit = tracked.t_admit or tracked.t_submit
                t_prefilled = tracked.t_prefill_done or t_admit
                self.tracer.event(
                    "serving_first_token", request=tracked.request_id,
                    trace=tracked.trace_id,
                    prompt_tokens=int(len(tracked.request.prompt_ids)),
                    chunks=(tracked.plan.n_chunks if tracked.plan else 0),
                    queue_wait_ms=(t_admit - tracked.t_submit) * 1000,
                    prefill_wait_ms=(t_prefilled - t_admit) * 1000,
                    first_tick_wait_ms=(t_now - t_prefilled) * 1000,
                )
                if self.prefix_cache is not None:
                    # TTFT split hit-vs-miss: the cache's whole point is
                    # this delta (summary()["prefix_cache"])
                    self.metrics.record_prefix_ttft(
                        t_now - tracked.t_submit,
                        hit=tracked.cache_hit is not None,
                    )
                gaps, t_prev = m - 1, t0
            else:
                gaps, t_prev = m, tracked.t_last_token
            if gaps:
                per_token_s = (t_now - t_prev) / m
                self.metrics.record_itl(per_token_s, gaps)
                tracked.itl_hist.record(per_token_s * 1000, gaps)
            tracked.t_last_token = t_now
        for slot in [s for s, t in self._slots.items()
                     if t.status is RequestStatus.FINISHED]:
            tracked = self._slots.pop(slot)
            self.pool = state_cache.evict(self.pool, slot)
            self._release_pages(slot, tracked)
            self._release_adapter_ref(tracked)
            # bounded finished-stream ring: lets stream_state() replay
            # a just-finished stream's tail to a re-attaching consumer
            # (SSE resume tokens) after the tracker is gone
            self._recent_finished[tracked.request_id] = (
                list(tracked.new_tokens), tracked.finish_reason
            )
            while len(self._recent_finished) > self.RECENT_FINISHED_KEEP:
                self._recent_finished.pop(
                    next(iter(self._recent_finished))
                )
            self._free.append(slot)
            if self.spec:
                self.drafter.forget(tracked.request_id)
            # a hot-swapped stream finishes as the internal continuation
            # request — the record and result must echo what the USER
            # submitted (original prompt; the full generated suffix
            # already lives in tracked.new_tokens)
            r = tracked.orig_request or tracked.request
            request_record = {
                "request_id": tracked.request_id,
                "trace_id": tracked.trace_id,
                "prompt_tokens": int(len(r.prompt_ids)),
                "new_tokens": len(tracked.new_tokens),
                "finish_reason": tracked.finish_reason,
                "queue_wait_ms": round(
                    (tracked.t_admit - tracked.t_submit) * 1000, 3),
                "ttft_ms": round(
                    (tracked.t_first_token - tracked.t_submit) * 1000, 3),
                "e2e_ms": round((t_now - tracked.t_submit) * 1000, 3),
                "itl_hist": tracked.itl_hist.to_dict(),
            }
            # cache/priority stamps only when the features are live, so
            # records from plain engines stay byte-stable
            if self.prefix_cache is not None:
                request_record["prefix_hit"] = tracked.cache_hit
            if tracked.preempted:
                request_record["preemptions"] = tracked.preempted
            if tracked.migrations:
                # the disaggregated handoff trail: how many times this
                # request moved tiers, the host time the moves cost,
                # and the prefill replica that produced the artifact
                # (this record's own `replica` stamp is the target)
                request_record["migrations"] = tracked.migrations
                request_record["migration_ms"] = round(
                    tracked.migration_ms, 3)
                request_record["migration_source"] = \
                    tracked.migration_source
            if tracked.priority != self.scheduler.default_priority:
                request_record["priority"] = tracked.priority
            if self.lora and getattr(tracked.request, "adapter", None):
                # the adapter the stream FINISHED under (the swapped-to
                # version for hot-swapped streams)
                request_record["adapter"] = tracked.request.adapter
            if tracked.hot_swaps:
                request_record["hot_swaps"] = tracked.hot_swaps
            self.metrics.record_request(request_record)
            if self.slo is not None:
                self.slo.observe_request(request_record,
                                         replica=self.metrics.replica)
            if self.retain_results:
                self.results[tracked.request_id] = GenerationResult(
                    request_id=tracked.request_id,
                    prompt_ids=r.prompt_ids,
                    new_tokens=np.asarray(tracked.new_tokens, np.int32),
                    finish_reason=tracked.finish_reason,
                )
        self._free.sort()
        kv_gauges = {}
        if self.hybrid:
            # KV-page gauges ride the serving_tick record (rendered by
            # scripts/obs_report.py): occupancy of the page pool plus
            # this window's allocator churn
            kv_gauges = dict(
                kv_pages_used=self.page_pool.pages_in_use,
                kv_pages_capacity=self.page_pool.num_pages,
                kv_page_allocs=self._page_allocs,
                kv_page_frees=self._page_frees,
            )
            self._page_allocs = 0
            self._page_frees = 0
        pc_gauges = {}
        if self.prefix_cache is not None:
            # hit/miss/bytes gauges ride the serving_tick record (host-
            # side only; absent entirely on cache-off engines)
            pc_gauges = dict(
                prefix_hits=self._pc_hits,
                prefix_misses=self._pc_misses,
                prefix_saved_tokens=self._pc_saved_tokens,
                prefix_cache_entries=len(self.prefix_cache),
                prefix_cache_bytes=self.prefix_cache.nbytes,
            )
            self._pc_hits = 0
            self._pc_misses = 0
            self._pc_saved_tokens = 0
        spec_gauges = {}
        if self.spec:
            # draft/accept counters ride every tick record when
            # speculation is on (absent at K=0 — records byte-stable);
            # obs_report.py renders the "speculation:" roll-up line
            spec_gauges = dict(
                spec_drafted=self._spec_drafted,
                spec_accepted=self._spec_accepted,
                spec_streams=self._spec_streams,
            )
            self._spec_drafted = 0
            self._spec_accepted = 0
            self._spec_streams = 0
        lora_gauges = {}
        if self.lora:
            # adapter-cache window counters + residency/live gauges
            # ride every tick record when multi-tenant LoRA is on
            # (absent otherwise — records stay byte-stable); the
            # distinct-adapter gauge counts the factor rows this
            # tick's launch actually mixed
            ac = self.adapter_cache
            lora_gauges = dict(
                adapters_resident=ac.resident_count,
                adapter_cache_hits=ac.hits - self._ad_hits0,
                adapter_cache_misses=ac.misses - self._ad_misses0,
                adapter_cache_evictions=ac.evictions
                - self._ad_evictions0,
                adapters_live=len({
                    t.adapter_slot for t in self._slots.values()
                    if t.status is RequestStatus.DECODE
                    and t.adapter_slot
                }),
            )
            self._ad_hits0 = ac.hits
            self._ad_misses0 = ac.misses
            self._ad_evictions0 = ac.evictions
        session_gauges = {}
        if self.session_store is not None:
            # durable-session gauges + window counters ride every tick
            # record when a store is attached (absent otherwise —
            # records stay byte-stable with sessions off); the TTL
            # sweep piggybacks here, rate-limited inside the store
            expired = self.session_store.maybe_sweep()
            if expired:
                self._session_expires += expired
                self.metrics.record_session_expire(expired)
            st = self.session_store.stats()
            session_gauges = dict(
                sessions_parked_host=st["parked_host"],
                sessions_parked_disk=st["parked_disk"],
                sessions_bytes_host=st["bytes_host"],
                sessions_bytes_disk=st["bytes_disk"],
                session_parks=self._session_parks,
                session_resumes=self._session_resumes,
                session_expires=self._session_expires,
            )
        quant_gauges = {}
        if self.quantized_weights or self.quantized_kv:
            # int8 serving stamps its dtype pair + resident-bytes
            # gauges on every tick record (absent otherwise — records
            # stay byte-stable with quant off)
            quant_gauges = dict(
                quantized=self._quant_stamp,
                weight_bytes=self._weight_bytes,
                page_pool_bytes=self._pool_bytes,
            )
        compile_gauges = {}
        if self.compile_watchdog is not None:
            # XLA compiles observed since the previous tick record
            # (absent without a watchdog — records stay byte-stable)
            n_compiles, compile_ms = self.compile_watchdog.drain()
            compile_gauges = dict(compiles=n_compiles,
                                  compile_ms=compile_ms)
        if self.tick_regression is not None:
            self.tick_regression.observe_tick(
                dt * 1000, replica=self.metrics.replica
            )
        self.metrics.record_tick(
            occupied=occupied, queue_depth=self.scheduler.depth,
            tokens_emitted=len(events), dt_s=dt,
            prefill_stall_ms=self._pending_stall_ms,
            prefill_chunk_tokens=self._pending_chunk_tokens,
            prefill_chunk_ms=self._pending_chunk_ms,
            prefill_real_tokens=self._pending_chunk_real_tokens,
            prefill_oneshot_tokens=self._pending_oneshot_real_tokens,
            prefill_oneshot_lanes=self._pending_oneshot_lanes,
            # goodput honesty: lanes are billed at the width the launch
            # actually computed, the tick's rung
            slot_lanes=width
            * (self.spec_width if self.spec else self.tokens_per_tick),
            compaction_width=width,
            traces=live_traces,
            model_shards=(self.model_shards if self.model_shards > 1
                          else None),
            # pipeline stamps only when the stage axis is live, so 2-D
            # engines' records stay byte-stable; bubble_lanes is 0 on
            # GSPMD-fallback ticks (no explicit clock, no ramp waste)
            stage_shards=(self.stage_shards if self.stage_shards > 1
                          else None),
            bubble_lanes=(bubble_lanes if self.stage_shards > 1
                          else None),
            preemptions=self._preemptions,
            migrations_out=self._migrations_out,
            migrations_in=self._migrations_in,
            # stamped only when nonzero (utils/metrics.record_tick) —
            # quota-off / swap-free engines' records stay byte-stable
            tenant_quota_stalls=self._quota_stalls,
            adapter_hot_swaps=self._hot_swaps,
            **pc_gauges,
            **kv_gauges,
            **quant_gauges,
            **spec_gauges,
            **lora_gauges,
            **session_gauges,
            **compile_gauges,
        )
        self._preemptions = 0
        self._migrations_out = 0
        self._migrations_in = 0
        self._quota_stalls = 0
        self._hot_swaps = 0
        self._session_parks = 0
        self._session_resumes = 0
        self._session_expires = 0
        self._pending_stall_ms = 0.0
        self._pending_chunk_tokens = 0
        self._pending_chunk_real_tokens = 0
        self._pending_chunk_ms = 0.0
        self._pending_oneshot_real_tokens = 0
        self._pending_oneshot_lanes = 0
        return events

    # ------------------------------------------------------------- frontends

    def serve(self, requests=()):  # -> Iterator[TokenEvent]
        """Minimal serving frontend: accept requests, stream tokens back.

        Yields TokenEvents as ticks complete; more requests may be
        ``submit``-ted concurrently from the consuming side between
        yields (the generator re-checks ``pending`` each tick).
        """
        for r in requests:
            self.submit(r)
        while self.pending:
            yield from self.step()

    def run(self, requests=()) -> list[GenerationResult]:
        """Submit ``requests``, drain the engine, return results in
        submission order."""
        if not self.retain_results:
            raise ValueError("run() needs retain_results=True; stream "
                             "via serve() instead")
        ids = [self.submit(r) for r in requests]
        for _ in self.serve():
            pass
        return [self.results[i] for i in ids]
