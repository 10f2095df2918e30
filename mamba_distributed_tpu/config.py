"""Configuration system for the TPU-native Mamba framework.

The reference (pie33000/mamba-distributed) has no config system: every
hyperparameter is a hard-coded constant (train.py:43-53,75,89-94,114;
dataloader.py:23; eval.py:14).  Here everything becomes a typed dataclass
field, with named presets for the five BASELINE.json configurations.

Model defaults mirror the semantics of ``mamba_ssm.models.config_mamba.
MambaConfig`` (mamba-ssm 2.2.2) plus the mixer defaults in
``modules/mamba_simple.py`` (Mamba-1) and ``modules/mamba2.py`` (Mamba-2),
which is what ``MambaConfig(d_model=768, vocab_size=50304)`` at
reference train.py:75 actually builds.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Mapping


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture config (reference: mamba_ssm MambaConfig + mixer defaults)."""

    d_model: int = 768
    n_layer: int = 64
    vocab_size: int = 50304
    # mamba_ssm MambaConfig.pad_vocab_size_multiple=8; 50304 is already padded.
    pad_vocab_size_multiple: int = 8
    # "mamba1" -> selective-scan mixer (what the reference's default ssm_cfg
    # builds, see SURVEY.md section 2.4); "mamba2" -> SSD mixer (the headline).
    ssm_layer: str = "mamba2"
    # 0 => no MLP between mixers (pure mixer stack, the reference default).
    d_intermediate: int = 0
    # --- MoE (beyond the reference; completes the parallelism menu with
    # expert parallelism over mesh.expert) ---
    # 0 => dense gated MLP; > 1 => the MLP becomes a token-choice top-k
    # mixture of experts of width ``d_intermediate`` each.  The layer is
    # DROPLESS (models/lm._moe_mlp): top-k of the router's float32 logits,
    # a float32 softmax over the chosen, every chosen row computed whatever
    # an expert's load; static shapes on every entry, training and serving
    moe_num_experts: int = 0
    moe_top_k: int = 2
    # The experts THIS program holds of each layer: ``moe_experts_held`` of
    # them from ``moe_first_expert`` on (0 held => all).  The router keeps
    # its width ``moe_num_experts`` and its ``moe_top_k`` choices a token;
    # the layer computes the part of the routed sum its own experts give
    # and leaves the rest out (the chip's share of a deployment whose other
    # experts live elsewhere; no exchange is run and nothing stands in for
    # the absent ones).
    moe_first_expert: int = 0
    moe_experts_held: int = 0
    # width of a shared expert, a gated MLP every token passes, added to
    # the routed sum (0 => none)
    moe_shared_intermediate: int = 0
    # weight of the Switch/GShard load-balance aux loss added by lm_loss
    moe_aux_weight: float = 0.01
    rms_norm: bool = True
    residual_in_fp32: bool = True
    tie_embeddings: bool = True
    norm_eps: float = 1e-5

    # --- shared mixer knobs (mamba_simple.py / mamba2.py defaults) ---
    d_state: int = 0  # 0 => auto: 16 for mamba1, 128 for mamba2
    d_conv: int = 4
    expand: int = 2
    # the mixer's inner width where a published config STATES it
    # (Falcon-H1's ``mamba_d_ssm``); 0 => ``expand * d_model``
    d_ssm: int = 0
    conv_bias: bool = True
    proj_bias: bool = False
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_init_floor: float = 1e-4

    # --- mamba1-only ---
    dt_rank: int = 0  # 0 => auto: ceil(d_model / 16)
    dt_init: str = "random"  # "random" | "constant"
    dt_scale: float = 1.0

    # --- mamba2-only ---
    headdim: int = 64
    ngroups: int = 1
    chunk_size: int = 256
    a_init_min: float = 1.0
    a_init_max: float = 16.0
    d_has_hdim: bool = False

    # --- hybrid (Jamba-style) attention layers; empty => pure SSM stack ---
    attn_layer_idx: tuple[int, ...] = ()
    attn_num_heads: int = 0  # 0 => auto: d_model // 64
    attn_num_kv_heads: int = 0  # 0 => same as attn_num_heads (MHA)
    attn_head_dim: int = 0  # 0 => auto: d_model // num_heads
    # -1 => full head dim; 0 => NO rotary (mamba_ssm MHA's rotary_emb_dim
    # convention, so imported hybrid configs keep their semantics)
    attn_rotary_dim: int = -1
    rope_theta: float = 10000.0
    # Falcon-H1's block: EVERY layer runs a Mamba-2 mixer and attention
    # on the one normed input and adds their scaled outputs into the
    # residual, so a layer owns a conv window, an SSM state and KV pages
    # at once.  ``attn_layer_idx`` is then every layer (the layers that
    # own KV pages), and the parameters are ONE stack, ``blocks``, whose
    # entries hold ``mixer`` and ``attn`` side by side (models/lm.py).
    attn_parallel: bool = False
    # --- scalar multipliers (muP), under the names Falcon-H1's published
    # config gives them; 1.0 / () apply nothing and trace nothing ---
    embedding_multiplier: float = 1.0  # on the embedding's rows
    lm_head_multiplier: float = 1.0  # on the logits
    ssm_in_multiplier: float = 1.0  # on the Mamba-2 mixer's input
    # on the five segments z | x | B | C | dt of the in-projection's output
    ssm_multipliers: tuple[float, ...] = ()
    ssm_out_multiplier: float = 1.0  # parallel block: on the mixer's output
    attention_in_multiplier: float = 1.0  # parallel block: on attention's input
    attention_out_multiplier: float = 1.0  # parallel block: on attention's output
    key_multiplier: float = 1.0  # on the keys, before RoPE
    # on each half-block's output (the mixer's or attention's, the MLP's or
    # the expert layer's) before it is added to the residual stream
    residual_multiplier: float = 1.0
    # the softmax scale of attention where a config STATES one; 0 => the
    # usual 1 / sqrt(head_dim).  Applied to the queries as
    # ``attention_multiplier * sqrt(head_dim)``, so that every attention
    # path's own 1 / sqrt(head_dim) gives the stated scale
    # (models/attention._split_qkv)
    attention_multiplier: float = 0.0
    # on the gated MLP's gate (before the SiLU) and on its down-projection
    mlp_multipliers: tuple[float, ...] = ()
    # attention strategy under sequence parallelism: "ring" (KV rotates,
    # O(t/S) per-chip memory) or "ulysses" (all-to-all head sharding —
    # needs heads % seq == 0; parallel/ulysses.py)
    attn_sp_impl: str = "ring"
    # SDPA backend for full-sequence attention: "xla" (blockwise online-
    # softmax scan, ops/blockwise_attention.py) or "pallas" (flash kernel,
    # ops/pallas/attention_kernels.py — skips fully-masked blocks).  Under
    # SP, ulysses runs flash after its head all-to-all and ring runs the
    # flash pair kernels per hop (fully-future hops skipped outright).
    # Decode steps always use the tiny-t XLA path.  "auto" (default)
    # resolves to "pallas" on TPU and "xla" elsewhere
    # (ops/pallas/common.py:resolve_attn_impl).
    attn_impl: str = "auto"

    # --- precision policy (reference: bf16 autocast + fp32 master weights,
    # train.py:72,142,211) ---
    compute_dtype: str = "bfloat16"
    param_dtype: str = "float32"

    # --- init ---
    initializer_range: float = 0.02  # embedding init std (mamba_ssm _init_weights)
    rescale_prenorm_residual: bool = True

    # --- memory ---
    remat: bool = True  # per-block activation checkpointing
    # "all": recompute everything (min memory); "dots": save matmul
    # outputs, recompute elementwise (jax dots_with_no_batch_dims policy —
    # trades HBM for a lighter backward); "mixer": save only the
    # scan/attention outputs so the backward never recomputes the SSD
    # scan (checkpoint_name "mixer_out" in the mixers)
    remat_policy: str = "all"

    # --- kernel backend for the SSD scan: "xla" (einsum formulation) or
    # "pallas" (fused VMEM kernels, ops/pallas/) ---
    ssm_impl: str = "xla"

    # causal-conv formulation: "shift" (width shifted multiply-adds) or
    # "xla_conv" (grouped conv_general_dilated — XLA's dedicated
    # depthwise path; sweepable, same math)
    conv_impl: str = "shift"

    # --- LM-head + CE formulation: "dense" (one head matmul, logits
    # materialized once in bf16) or "blocked" (vocab-blocked online
    # logsumexp, ops/loss.py — no (b, t, V) tensor ever exists; frees
    # ~0.8 GB at B=8 / ~3.3 GB at the reference's B=32) ---
    loss_impl: str = "dense"
    loss_vocab_blocks: int = 8

    # --- chunked prompt prefill (serving/prefill.py; pure-SSM only) ---
    # Prompts longer than this many tokens prefill as fixed-size chunks
    # threaded through the mixers' initial_conv_state/initial_ssm_state
    # carries: one compiled chunk shape regardless of prompt length
    # (instead of one pow2 bucket trace per length class, and instead of
    # up-to-2x pow2 padding waste), and the serving engine can interleave
    # a long prompt's chunks with decode ticks.  Lives on ModelConfig —
    # not an engine knob — so ``generate()`` and the engine always chunk
    # the same prompt identically (the token-parity contract, same rule
    # as the pow2 buckets).  Consumers read
    # ``effective_prefill_chunk_tokens``, which rounds this up to a
    # multiple of ``chunk_size`` for mamba2 (SSD chunk alignment).
    # 0 disables (always one-shot pow2-bucketed prefill).
    prefill_chunk_tokens: int = 256
    # --- paged attention KV cache (hybrid decode/serving; models/
    # attention.py, serving/state_cache.py, ops/pallas/attention_kernels
    # .py ragged decode kernel).  The decode-time KV cache is a pool of
    # fixed-size pages plus a per-row page table and per-row lengths, so
    # serving slots at different positions share one cache and KV HBM is
    # O(pages in use), not O(slots * max_len). ---
    # Tokens per KV page.  Must be a multiple of 8: padded-width masked
    # attention is bit-stable across page-count buckets only at 8-lane
    # granularity (the engine<->generate() exact-parity contract leans
    # on it), and 8 sublanes is the TPU tile granule anyway.
    kv_page_tokens: int = 64
    # Per-request KV budget in the SERVING pool: one slot's page-table
    # row holds ceil(kv_slot_tokens / kv_page_tokens) entries, so a
    # hybrid request needs prompt + max_new_tokens <= kv_slot_tokens.
    kv_slot_tokens: int = 1024
    # Total pages in the serving pool.  0 => auto: capacity * pages-per-
    # slot (every slot can run to kv_slot_tokens simultaneously — the
    # dense-equivalent worst case).  Set lower to oversubscribe HBM when
    # typical sequences are far shorter than kv_slot_tokens; admission
    # then waits for pages, never OOMs mid-flight (pages for the whole
    # request are reserved up front, serving/engine.py).
    kv_pool_pages: int = 0
    # Serving-engine interleaving budget: max prefill-chunk tokens
    # dispatched between two decode ticks (serving/engine.py).  Bounds
    # the tick-to-tick stall a long prompt can inject (ITL of running
    # slots) while it streams in.  0 => unbounded (a whole prompt
    # prefills between two ticks, the pre-chunking behavior).
    prefill_tokens_per_tick: int = 512
    # How the per-tick chunk budget is scheduled across concurrent
    # partial prefills (serving/engine.py): "rr" rotates one chunk at a
    # time in admission order; "srpt" grants the prompt with the FEWEST
    # remaining chunks first (shortest-remaining-processing-time — a
    # nearly-done prompt reaches its first token before a fresh long one
    # begins), with a starvation guard so a long prompt still gets a
    # chunk at least every few grants.
    prefill_schedule: str = "rr"
    # --- data-parallel serving fabric (serving/router.py) ---
    # Engine replicas the request router places over (least-loaded
    # placement; each replica is a full ServingEngine with its own slot
    # pool).  The router/bench default; 1 => a single engine.
    serving_replicas: int = 1
    # Shards of the serving slot pool's batch axis over `mesh.data`
    # (parallel/mesh.serving_mesh): slot/page state and the decode
    # tick's batch axis partition over the data axis via NamedSharding
    # (weights replicated), so one engine spans every device in the
    # mesh.  1 => single-device pool (the pre-fabric behavior).
    # capacity must divide evenly across the shards.
    serving_data_shards: int = 1
    # --- disaggregated prefill/decode tiers (serving/router.py,
    # serving/replica.py role=) ---
    # Prompt-length cutoff (tokens) above which the router places a
    # request on the PREFILL tier (EngineReplica(role="prefill")): the
    # replica runs the chunked prefill, then at prefill-complete the
    # request's O(1) carry snapshot (+ hybrid KV pages) MIGRATES to a
    # decode-tier replica where state_cache.restore resumes the stream
    # bit-exactly — long prompts stop taxing short-request ITL on the
    # decode tier (docs/SERVING.md "Disaggregated tiers").  0 (default)
    # disables role-aware routing: every replica serves mixed, the
    # exact pre-disagg fabric.
    disagg_prompt_threshold: int = 0
    # --- prefix-state cache + preemption (serving/prefix_cache.py,
    # serving/engine.py) ---
    # Prefix-state cache entry cap: chunk-boundary conv/SSM carry
    # snapshots (and full-prompt state+logits pairs) keyed by
    # prompt-prefix hash, so requests sharing a system prompt / few-
    # shot preamble skip the shared prefill work — near-zero TTFT on
    # full hits.  0 disables (the default: the cache pins device
    # buffers alive and — for hybrids — holds KV page refs past
    # request eviction, so it is opt-in).  Hybrid caches are engine-
    # private (entries pin the engine's own page pool).
    prefix_cache_entries: int = 0
    # Byte cap over cached state (carries + logits + pinned KV page
    # bytes); LRU evicts over either cap.  0 => entry cap only.
    prefix_cache_bytes: int = 0
    # Promotion threshold: a prefix must MISS this many lookups before
    # its snapshot is stored (1 = store on first sight; raise to keep
    # one-off prompts from churning the LRU).
    prefix_min_chunk_hits: int = 1
    # Priority a request defaults to when GenerationRequest.priority
    # is None (higher = more important).  When a higher-priority
    # request is queued with no free slot, the engine preempts the
    # lowest-priority DECODING slot: its carry swaps to host RAM (KV
    # page refs held — no page churn) and it resumes later without
    # re-prefill, mid-stream, bit-exactly.
    serving_default_priority: int = 0
    # --- quantized serving (ops/quant.py; docs/SERVING.md "Quantized
    # serving") ---
    # Serving/decode weight dtype.  "bf16" (default) is the byte-stable
    # status quo: the decode cast (inference/generate._decode_params)
    # casts matmul kernels + embedding to ``compute_dtype`` exactly as
    # before.  "int8" quantizes the same leaves symmetric per-channel
    # (q int8 + f32 scale per output column for column-parallel params,
    # per input row for row-parallel, per vocab row for the embedding/
    # head — the scale axis is always the tensor-parallel axis, so
    # scales shard with their weight and no cross-shard rescale is ever
    # needed) and the matmul sites dequantize AT USE: ``(x @ q) * scale``
    # / ``(x * scale) @ q``, fused by XLA — no materialized full-
    # precision weight copy.  Both the serving engine and ``generate()``
    # read this knob through the ONE shared decode cast, so quantized
    # engine==generate() parity holds by construction (toleranced —
    # ``ops/quant.assert_stream_close``).
    serving_weight_dtype: str = "bf16"
    # KV page-pool dtype (hybrid stacks).  "bf16" (default) stores
    # pages in ``compute_dtype`` — the byte-stable status quo.  "int8"
    # stores int8 pages with one f32 scale per (physical page, kv head)
    # alongside the head-major pools; the ragged Pallas kernels fuse
    # the dequant into the scalar-prefetched page walk (read int8 tile
    # -> multiply by scale in-register) and prefill's fused page WRITE
    # quantizes the chunk's K/V before the one-hot merge.  Halves page
    # bytes => ~2x pages per chip at fixed pool HBM (the
    # ``quant_kv_capacity`` bench row).
    kv_page_dtype: str = "bf16"
    # --- speculative decoding (serving/spec_decode.py; docs/SERVING.md
    # "Speculative decoding") ---
    # Draft tokens verified per serving tick.  0 (default) disables —
    # the byte-stable status quo: one token per slot per launch.  K > 0
    # turns every decode tick into a K-token draft/verify step: a
    # drafter proposes K cheap continuation guesses per slot and ONE
    # chunk-machinery launch (models/lm.lm_verify_chunk) scores all
    # K+1 positions at once, committing the longest correct prefix —
    # up to K+2 tokens per full-model weight read instead of 1.
    # Greedy-only (requests must use top_k=1; speculation is lossless
    # under argmax — streams stay token-identical to non-speculative
    # greedy).  Both the serving engine and ``generate()`` read this
    # knob, so the two paths speculate identically (the parity
    # contract, tests/test_spec_decode.py).
    spec_tokens: int = 0
    # Who proposes the K draft tokens: "ngram" (host-side prompt-lookup
    # cache over each stream's own prompt + emitted tokens — free, and
    # strong on repetitive/code-like text) or "model" (a small
    # companion LM running the same ``lm_step`` at a tiny config; the
    # engine/generate() take the ``Drafter`` instance since the
    # companion's params aren't derivable from this config).  Draft
    # quality only moves the acceptance rate, never the tokens.
    spec_drafter: str = "ngram"
    # Longest suffix n-gram the "ngram" drafter matches against the
    # stream's history before falling back to shorter ones.
    spec_ngram_order: int = 3
    # --- multi-tenant LoRA serving (serving/adapters.py; docs/
    # SERVING.md "Multi-tenant LoRA") ---
    # Named LoRA adapters one engine may serve concurrently.  0
    # (default) disables multi-tenancy entirely — the byte-stable
    # status quo: no factor pools ride the params, no record stamps,
    # identical traces.  > 0 enables the segmented batched-LoRA path:
    # an AdapterRegistry holds up to this many named adapters' low-rank
    # {A, B} factors over the linear()-routed projections, a bounded
    # device AdapterCache stacks them into (slots+1, ...) factor pools
    # (row 0 = the zero "no adapter" factors), and every tick computes
    # ``y = base(x) + (x @ A[ids]) @ B[ids]`` with per-slot adapter ids
    # gathered from the slot pool's meta — slots running DIFFERENT
    # adapters share ONE compiled launch.  Parity regime: a stream
    # under adapter a matches solo ``generate()`` on the MERGED weights
    # ``W + (alpha/rank)·A@B`` via ops/quant.assert_stream_close
    # (toleranced — the segmented delta re-associates float sums, so
    # bit-exactness is the wrong pin; greedy tokens agree exactly on
    # the fp32 CPU matrix, tests/test_tenant_lora.py).
    lora_max_adapters: int = 0
    # Low-rank dimension r shared by every adapter on the engine (the
    # factor pools are static-shape).
    lora_rank: int = 8
    # Default LoRA scaling numerator: the delta is weighted alpha/rank
    # (per-adapter alpha may override at registration; the scale is
    # folded into the stored B factors once, so the hot path never
    # multiplies by it).
    lora_alpha: float = 16.0
    # Device factor-pool slots (adapters resident on-device at once).
    # 0 => auto: lora_max_adapters (every registered adapter resident).
    # Set lower to page adapters: admission reserves a slot like it
    # reserves KV pages (waits when all slots are pinned by resident
    # streams — never a mid-flight miss), refcounts pin a slot while
    # any stream uses it, and zero-ref residents evict LRU.
    lora_cache_slots: int = 0
    # Tensor-parallel shards of the serving WEIGHTS over `mesh.model`
    # (the 2-D serving mesh's second axis): Mamba d_inner channels,
    # attention heads and the embedding/head vocab axis split across
    # devices (parallel/sharding.serving_param_specs), so one engine
    # can serve a model bigger than a single device and each device
    # reads 1/N of the weights per decode tick (decode's binding
    # resource).  1 => weights replicated (the exact pre-TP layout:
    # same shardings, same trace counts).  d_inner, padded vocab and
    # (hybrid) head counts must divide evenly — checked with a clear
    # error at engine construction.
    serving_model_shards: int = 1
    # Pipeline-parallel shards of the serving LAYER STACK over
    # `mesh.stage` (the 3-D serving mesh's middle axis,
    # parallel/mesh.serving_mesh): the scan-over-layers parameter
    # stacks AND the slot pool's per-layer conv/SSM carries + KV page
    # pools shard their leading layer axis across stages
    # (parallel/sharding.serving_param_specs / slot_pool_specs), so
    # each stage holds only its own layers' weights and state — the
    # second way (after serving_model_shards) one engine serves a
    # model bigger than a single device, composable with both other
    # axes.  Pure-SSM single-data-shard engines additionally run the
    # decode tick as a GPipe-microbatched schedule over the lane
    # bucket (parallel/pipeline.pipelined_decode_layers).  1 => the
    # exact 2-D status quo: serving_mesh stays ("data", "model") and
    # no spec ever names a stage axis (same shardings, same traces).
    # n_layer (and each hybrid stack family) must divide evenly —
    # checked with a clear error at engine construction.
    serving_stage_shards: int = 1
    # Durable session store (docs/SERVING.md "Durable sessions"):
    # parked sessions' time-to-live in seconds — the background sweeper
    # reaps older ones (0 = park forever; explicit parks may override
    # per call) — and the host-RAM tier's byte budget, above which the
    # LRU parked sessions demote to the disk tier (0 = write-through:
    # everything demotes immediately when a disk tier exists).  Both
    # only take effect where a store is constructed (--state-dir on
    # serve_worker/serve_fabric, or session_store= in code); the
    # default engine/router path carries no store and is byte-stable.
    session_ttl_s: float = 0.0
    session_host_bytes: int = 0
    # --- elastic serving fabric (serving/autoscale/; docs/SERVING.md
    # "Elastic fabric") ---
    # Admission control: fabric-wide queued-request cap above which the
    # router sheds new submits (the named AdmissionRejected -> HTTP 429
    # + Retry-After), and the default per-request queue deadline in
    # milliseconds (requests carrying queue_deadline_ms=None inherit
    # it; shed when the estimated wait exceeds it).  Both 0 (default)
    # = admission control off, the byte-stable status quo.
    admission_queue_cap: int = 0
    admission_deadline_ms: float = 0.0
    # Autoscaling: per-tier fleet ceiling for the AutoscaleController
    # (0 = autoscaling off — the fleet stays operator-sized) and floor,
    # cooldowns after scale-up / any scaling action before the next
    # up / down, consecutive pressured (breached-or-deep-queue) and
    # healthy evaluations before acting (flap absorption), and the
    # mean-queued-per-accepting-replica thresholds that count as
    # pressure / health (the band between them is hysteresis dead
    # zone).  serving/autoscale/controller.AutoscalePolicy validates
    # the cross-field constraints; these knobs only feed it.
    autoscale_max_replicas: int = 0
    autoscale_min_replicas: int = 1
    autoscale_up_cooldown_s: float = 5.0
    autoscale_down_cooldown_s: float = 30.0
    autoscale_breach_evals: int = 3
    autoscale_clear_evals: int = 10
    autoscale_queue_high: float = 2.0
    autoscale_queue_low: float = 0.5
    # --- online per-tenant LoRA tuning (serving/tuning/; docs/
    # SERVING.md "Online adapter tuning") ---
    # Per-tenant fairness quota: max concurrent resident slots one
    # adapter BASE name (any version) may hold on an engine.  0
    # (default) = no quota, the byte-stable status quo.  > 0 makes
    # admission REQUEUE (never shed) a request whose tenant already
    # holds this many slots — the named
    # serving.scheduler.TenantQuotaExceeded deferral, so one hot
    # tenant cannot starve the rest of the slot pool.
    tenant_max_slots: int = 0
    # A/B routing for freshly tuned adapter versions: the fraction of
    # BARE-name requests routed to the tenant's LATEST version; the
    # rest pin the previous one (a deterministic per-request hash of
    # the sampling seed picks the arm, so retries land on the same
    # version).  1.0 (default) routes everyone to the latest — with a
    # single version that is the exact PR-15 status quo.  Explicit
    # ``name@vN`` requests always bypass the split.
    lora_ab_fraction: float = 1.0
    # Online tune-job train-step knobs (serving/tuning/trainer.py):
    # optimizer steps per job (one batch per step, examples cycled),
    # Adam learning rate over the factor leaves, examples per batch,
    # and the fixed sequence length examples are right-padded /
    # truncated to (static shapes keep ONE compiled masked step per
    # fabric).  Inert until a trainer-role replica exists.
    tune_steps: int = 20
    tune_lr: float = 1e-3
    tune_batch_size: int = 4
    tune_seq_len: int = 64

    def __post_init__(self):
        if self.attn_parallel and (
            self.ssm_layer != "mamba2"
            or tuple(self.attn_layer_idx) != tuple(range(self.n_layer))
        ):
            raise ValueError(
                "attn_parallel puts a Mamba-2 mixer and attention in every "
                "block: it needs ssm_layer='mamba2' and attn_layer_idx = "
                f"every layer, got {self.ssm_layer!r} and "
                f"{tuple(self.attn_layer_idx)} of {self.n_layer} layers"
            )
        if self.d_ssm % self.headdim:
            raise ValueError(
                f"d_ssm={self.d_ssm} must be whole heads of "
                f"headdim={self.headdim}"
            )
        for name, n in (("ssm_multipliers", 5), ("mlp_multipliers", 2)):
            if len(getattr(self, name)) not in (0, n):
                raise ValueError(
                    f"{name} holds {n} values or none, got "
                    f"{getattr(self, name)}"
                )
        if self.remat_policy not in ("all", "dots", "mixer"):
            raise ValueError(
                f"remat_policy must be 'all', 'dots' or 'mixer', got "
                f"{self.remat_policy!r}"
            )
        if self.ssm_impl not in ("xla", "pallas"):
            raise ValueError(
                f"ssm_impl must be 'xla' or 'pallas', got {self.ssm_impl!r}"
            )
        if self.ssm_impl == "pallas" and self.ssm_layer not in ("mamba1", "mamba2"):
            raise ValueError(
                "ssm_impl='pallas' backs the SSD scan (mamba2) and the "
                f"selective scan (mamba1); got ssm_layer={self.ssm_layer!r}"
            )
        if self.attn_sp_impl not in ("ring", "ulysses"):
            raise ValueError(
                f"attn_sp_impl must be 'ring' or 'ulysses', got "
                f"{self.attn_sp_impl!r}"
            )
        if self.conv_impl not in ("shift", "xla_conv"):
            raise ValueError(
                f"conv_impl must be 'shift' or 'xla_conv', got "
                f"{self.conv_impl!r}"
            )
        if self.loss_impl not in ("dense", "blocked"):
            raise ValueError(
                f"loss_impl must be 'dense' or 'blocked', got "
                f"{self.loss_impl!r}"
            )
        if self.loss_impl == "blocked" and (
            self.loss_vocab_blocks < 1
            or self.vocab_size_padded % self.loss_vocab_blocks != 0
        ):
            raise ValueError(
                f"loss_vocab_blocks={self.loss_vocab_blocks} must be a "
                f"positive divisor of padded vocab {self.vocab_size_padded}"
            )
        if self.prefill_chunk_tokens < 0:
            raise ValueError(
                f"prefill_chunk_tokens must be >= 0 (0 disables chunked "
                f"prefill), got {self.prefill_chunk_tokens}"
            )
        if self.prefill_tokens_per_tick < 0:
            raise ValueError(
                f"prefill_tokens_per_tick must be >= 0 (0 => unbounded), "
                f"got {self.prefill_tokens_per_tick}"
            )
        if self.prefill_schedule not in ("rr", "srpt"):
            raise ValueError(
                f"prefill_schedule must be 'rr' or 'srpt', got "
                f"{self.prefill_schedule!r}"
            )
        if self.serving_replicas < 1:
            raise ValueError(
                f"serving_replicas must be >= 1, got {self.serving_replicas}"
            )
        if self.serving_data_shards < 1:
            raise ValueError(
                f"serving_data_shards must be >= 1, got "
                f"{self.serving_data_shards}"
            )
        if self.serving_model_shards < 1:
            raise ValueError(
                f"serving_model_shards must be >= 1, got "
                f"{self.serving_model_shards}"
            )
        if self.serving_stage_shards < 1:
            raise ValueError(
                f"serving_stage_shards must be >= 1, got "
                f"{self.serving_stage_shards}"
            )
        if self.disagg_prompt_threshold < 0:
            raise ValueError(
                f"disagg_prompt_threshold must be >= 0 (0 disables "
                f"role-aware routing), got {self.disagg_prompt_threshold}"
            )
        if self.prefix_cache_entries < 0:
            raise ValueError(
                f"prefix_cache_entries must be >= 0 (0 disables the "
                f"prefix-state cache), got {self.prefix_cache_entries}"
            )
        if self.prefix_cache_bytes < 0:
            raise ValueError(
                f"prefix_cache_bytes must be >= 0 (0 => entry cap only), "
                f"got {self.prefix_cache_bytes}"
            )
        if self.prefix_min_chunk_hits < 1:
            raise ValueError(
                f"prefix_min_chunk_hits must be >= 1 (store on first "
                f"sight), got {self.prefix_min_chunk_hits}"
            )
        if self.kv_page_tokens < 8 or self.kv_page_tokens % 8:
            raise ValueError(
                f"kv_page_tokens must be a positive multiple of 8 (page-"
                f"bucketed masked attention is bit-stable only at 8-lane "
                f"granularity), got {self.kv_page_tokens}"
            )
        if self.kv_slot_tokens < self.kv_page_tokens:
            raise ValueError(
                f"kv_slot_tokens={self.kv_slot_tokens} must hold at least "
                f"one page of kv_page_tokens={self.kv_page_tokens}"
            )
        if self.kv_pool_pages < 0:
            raise ValueError(
                f"kv_pool_pages must be >= 0 (0 => auto-size from "
                f"capacity), got {self.kv_pool_pages}"
            )
        if self.serving_weight_dtype not in ("bf16", "int8"):
            raise ValueError(
                f"serving_weight_dtype must be 'bf16' (the compute-dtype "
                f"decode cast, the status quo) or 'int8', got "
                f"{self.serving_weight_dtype!r}"
            )
        if self.kv_page_dtype not in ("bf16", "int8"):
            raise ValueError(
                f"kv_page_dtype must be 'bf16' (compute-dtype pages, the "
                f"status quo) or 'int8', got {self.kv_page_dtype!r}"
            )
        if self.spec_tokens < 0:
            raise ValueError(
                f"spec_tokens must be >= 0 (0 disables speculative "
                f"decoding), got {self.spec_tokens}"
            )
        if self.spec_drafter not in ("ngram", "model"):
            raise ValueError(
                f"spec_drafter must be 'ngram' or 'model', got "
                f"{self.spec_drafter!r}"
            )
        if self.spec_ngram_order < 1:
            raise ValueError(
                f"spec_ngram_order must be >= 1, got "
                f"{self.spec_ngram_order}"
            )
        if self.lora_max_adapters < 0:
            raise ValueError(
                f"lora_max_adapters must be >= 0 (0 disables multi-"
                f"tenant LoRA serving), got {self.lora_max_adapters}"
            )
        if self.lora_max_adapters > 0:
            if self.lora_rank < 1:
                raise ValueError(
                    f"lora_rank must be >= 1 when LoRA serving is on, "
                    f"got {self.lora_rank}"
                )
            if self.lora_alpha <= 0:
                raise ValueError(
                    f"lora_alpha must be > 0, got {self.lora_alpha}"
                )
            if self.lora_cache_slots < 0:
                raise ValueError(
                    f"lora_cache_slots must be >= 0 (0 => auto: "
                    f"lora_max_adapters), got {self.lora_cache_slots}"
                )
        if self.tenant_max_slots < 0:
            raise ValueError(
                f"tenant_max_slots must be >= 0 (0 = no per-tenant "
                f"quota), got {self.tenant_max_slots}"
            )
        if not 0.0 <= self.lora_ab_fraction <= 1.0:
            raise ValueError(
                f"lora_ab_fraction must be in [0, 1] (the share of "
                f"bare-name requests routed to the latest adapter "
                f"version), got {self.lora_ab_fraction}"
            )
        if self.tune_steps < 1:
            raise ValueError(
                f"tune_steps must be >= 1, got {self.tune_steps}"
            )
        if self.tune_lr <= 0:
            raise ValueError(
                f"tune_lr must be > 0, got {self.tune_lr}"
            )
        if self.tune_batch_size < 1:
            raise ValueError(
                f"tune_batch_size must be >= 1, got "
                f"{self.tune_batch_size}"
            )
        if self.tune_seq_len < 1:
            raise ValueError(
                f"tune_seq_len must be >= 1, got {self.tune_seq_len}"
            )
        if self.session_ttl_s < 0:
            raise ValueError(
                f"session_ttl_s must be >= 0 (0 = parked sessions never "
                f"expire), got {self.session_ttl_s}"
            )
        if self.session_host_bytes < 0:
            raise ValueError(
                f"session_host_bytes must be >= 0 (0 = write-through to "
                f"the disk tier), got {self.session_host_bytes}"
            )
        if self.admission_queue_cap < 0:
            raise ValueError(
                f"admission_queue_cap must be >= 0 (0 = no cap), got "
                f"{self.admission_queue_cap}"
            )
        if self.admission_deadline_ms < 0:
            raise ValueError(
                f"admission_deadline_ms must be >= 0 (0 = no default "
                f"deadline), got {self.admission_deadline_ms}"
            )
        if self.autoscale_max_replicas < 0:
            raise ValueError(
                f"autoscale_max_replicas must be >= 0 (0 = autoscaling "
                f"off), got {self.autoscale_max_replicas}"
            )
        if self.autoscale_min_replicas < 1:
            raise ValueError(
                f"autoscale_min_replicas must be >= 1, got "
                f"{self.autoscale_min_replicas}"
            )
        if self.autoscale_max_replicas:
            # the cross-field policy constraints (min <= max, low <=
            # high, positive eval counts, non-negative cooldowns) live
            # with AutoscalePolicy — build one so a bad config fails
            # HERE at validation, not at the first controller tick
            self.autoscale_policy()
        if self.attn_impl not in ("auto", "xla", "pallas"):
            raise ValueError(
                f"attn_impl must be 'auto', 'xla' or 'pallas', got "
                f"{self.attn_impl!r}"
            )
        if self.moe_num_experts:
            if self.moe_num_experts < 2:
                raise ValueError("moe_num_experts must be 0 (dense) or >= 2")
            if self.d_intermediate <= 0:
                raise ValueError(
                    "MoE replaces the gated MLP: moe_num_experts > 0 needs "
                    "d_intermediate > 0"
                )
            if not 1 <= self.moe_top_k <= self.moe_num_experts:
                raise ValueError(
                    f"moe_top_k={self.moe_top_k} must be in "
                    f"[1, {self.moe_num_experts}]"
                )
        first, held = self.moe_first_expert, self.moe_experts_held
        if not (0 <= first and 0 <= held
                and first + held <= self.moe_num_experts):
            raise ValueError(
                f"the held experts [{first}, {first + held}) must lie within "
                f"the router's {self.moe_num_experts}"
            )
        if self.moe_shared_intermediate and not self.moe_num_experts:
            raise ValueError(
                "moe_shared_intermediate is the shared expert of a routed "
                "layer: it needs moe_num_experts > 0"
            )

    def autoscale_policy(self):
        """The ``serving.autoscale.AutoscalePolicy`` these knobs
        describe (its ``__post_init__`` validates the cross-field
        constraints).  Only meaningful with ``autoscale_max_replicas``
        > 0 — callers gate on that, this just packages the fields.
        Lazy import: config must stay importable without the serving
        stack."""
        from mamba_distributed_tpu.serving.autoscale.controller import (
            AutoscalePolicy,
        )

        return AutoscalePolicy(
            min_replicas=self.autoscale_min_replicas,
            max_replicas=self.autoscale_max_replicas,
            scale_up_cooldown_s=self.autoscale_up_cooldown_s,
            scale_down_cooldown_s=self.autoscale_down_cooldown_s,
            breach_evals_up=self.autoscale_breach_evals,
            clear_evals_down=self.autoscale_clear_evals,
            queue_depth_high=self.autoscale_queue_high,
            queue_depth_low=self.autoscale_queue_low,
        )

    @property
    def vocab_size_padded(self) -> int:
        m = self.pad_vocab_size_multiple
        return ((self.vocab_size + m - 1) // m) * m

    @property
    def d_inner(self) -> int:
        return self.d_ssm or self.expand * self.d_model

    def layer_mixers(self, i: int) -> tuple[bool, bool]:
        """(a Mamba mixer?, attention?) in layer ``i``: attention stands in
        the mixer's place, or beside it in a parallel block."""
        attn = i in self.attn_layer_idx
        return self.attn_parallel or not attn, attn

    @property
    def moe_held(self) -> tuple[int, int]:
        """(first, count) of the routed experts this program holds."""
        if self.moe_experts_held:
            return self.moe_first_expert, self.moe_experts_held
        return 0, self.moe_num_experts

    @property
    def n_mamba_layers(self) -> int:
        """Layers that own a conv window and an SSM state."""
        return sum(self.layer_mixers(i)[0] for i in range(self.n_layer))

    @property
    def effective_d_state(self) -> int:
        if self.d_state:
            return self.d_state
        return 128 if self.ssm_layer == "mamba2" else 16

    @property
    def effective_dt_rank(self) -> int:
        return self.dt_rank or math.ceil(self.d_model / 16)

    @property
    def effective_prefill_chunk_tokens(self) -> int:
        """Chunked-prefill chunk width actually used (0 => disabled).

        For mamba2 the configured width rounds UP to the next multiple
        of ``chunk_size`` so prefill-chunk boundaries always land on SSD
        chunk boundaries (a misaligned split would degrade the chunked
        scan via ``_divisor_chunk``), whatever a sweep sets
        ``chunk_size`` to.  Every chunked-prefill consumer — the serving
        engine, ``generate()``, the planner — reads THIS, never the raw
        field, so the two sides can never disagree on the layout.
        """
        c = self.prefill_chunk_tokens
        if c <= 0:
            return 0
        if self.ssm_layer == "mamba2" and c % self.chunk_size:
            return ((c + self.chunk_size - 1) // self.chunk_size) * self.chunk_size
        return c

    @property
    def effective_lora_cache_slots(self) -> int:
        """Device adapter-cache slots actually allocated (0 = LoRA
        off): ``lora_cache_slots``, or every registered adapter
        resident when the knob is 0."""
        if self.lora_max_adapters <= 0:
            return 0
        return self.lora_cache_slots or self.lora_max_adapters

    @property
    def kv_quantized(self) -> bool:
        """True when the paged attention KV pools store int8 pages with
        per-(page, kv-head) f32 scales (``kv_page_dtype="int8"``)."""
        return self.kv_page_dtype == "int8"

    @property
    def kv_pages_per_slot(self) -> int:
        """Page-table width of one serving slot (ceil of the per-request
        KV budget in pages)."""
        return -(-self.kv_slot_tokens // self.kv_page_tokens)

    @property
    def nheads(self) -> int:
        assert self.d_inner % self.headdim == 0
        return self.d_inner // self.headdim

    @property
    def effective_attn_num_heads(self) -> int:
        return self.attn_num_heads or self.d_model // 64

    @property
    def effective_attn_num_kv_heads(self) -> int:
        return self.attn_num_kv_heads or self.effective_attn_num_heads

    @property
    def effective_attn_head_dim(self) -> int:
        return self.attn_head_dim or self.d_model // self.effective_attn_num_heads

    def num_params(self) -> int:
        """Analytic parameter count (used for MFU and sanity checks)."""
        d, v = self.d_model, self.vocab_size_padded
        di, ds = self.d_inner, self.effective_d_state
        n = 0
        n += v * d  # embedding (tied head adds nothing)
        if not self.tie_embeddings:
            n += v * d
        for i in range(self.n_layer):
            n += d  # pre-norm scale
            mamba, attn = self.layer_mixers(i)
            if attn:
                nh = self.effective_attn_num_heads
                nkv = self.effective_attn_num_kv_heads
                hd = self.effective_attn_head_dim
                n += d * (nh * hd) + 2 * d * (nkv * hd) + (nh * hd) * d
            if mamba and self.ssm_layer == "mamba1":
                dtr = self.effective_dt_rank
                n += d * 2 * di  # in_proj
                n += di * self.d_conv + (di if self.conv_bias else 0)
                n += di * (dtr + 2 * ds)  # x_proj
                n += dtr * di + di  # dt_proj (+bias always)
                n += di * ds  # A_log
                n += di  # D
                n += di * d  # out_proj
            elif mamba:  # mamba2
                g, nh = self.ngroups, self.nheads
                d_in_proj = 2 * di + 2 * g * ds + nh
                conv_dim = di + 2 * g * ds
                n += d * d_in_proj
                n += conv_dim * self.d_conv + (conv_dim if self.conv_bias else 0)
                n += nh  # dt_bias
                n += nh  # A_log
                n += di if self.d_has_hdim else nh  # D
                n += di  # gated norm scale
                n += di * d  # out_proj
            if self.d_intermediate > 0:
                n += d  # second norm
                mlp = d * self.d_intermediate * 2 + self.d_intermediate * d
                if self.moe_num_experts:
                    n += d * self.moe_num_experts  # router, whole
                    n += self.moe_held[1] * mlp  # the experts held here
                    n += 3 * d * self.moe_shared_intermediate
                else:
                    n += mlp  # gated MLP
        n += d  # final norm
        return n


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Logical device mesh. Axis sizes of 1 collapse that axis.

    data  - pure data parallel (gradients psum'd, params replicated)
    fsdp  - data parallel + param/optimizer-state sharding (ZeRO-3 style)
    seq   - sequence/context parallelism (SSD chunk-state passing, ring attn)
    tensor- tensor parallelism over d_inner/heads
    pipe  - GPipe pipeline stages over the layer stack (the grad-accum
            microbatches feed the pipeline; parallel/pipeline.py)
    expert- expert parallelism: MoE expert-stacked MLP weights shard
            their expert axis here; tokens are batch-sharded over it too
            (an extra pure-DP axis for the non-MoE layers), so the MoE
            dispatch/combine einsums become GSPMD all-to-alls
    """

    data: int = 1
    fsdp: int = 1
    seq: int = 1
    tensor: int = 1
    pipe: int = 1
    expert: int = 1

    @property
    def num_devices(self) -> int:
        return (self.data * self.fsdp * self.seq * self.tensor * self.pipe
                * self.expert)

    @property
    def axis_names(self) -> tuple[str, ...]:
        return ("data", "fsdp", "seq", "tensor", "pipe", "expert")

    @property
    def shape(self) -> tuple[int, ...]:
        return (self.data, self.fsdp, self.seq, self.tensor, self.pipe,
                self.expert)


@dataclasses.dataclass(frozen=True)
class DataConfig:
    """Token-shard data pipeline (reference: dataloader.py)."""

    data_dir: str = "edu_fineweb10B"  # reference dataloader.py:23
    # If True and data_dir is missing, generate deterministic synthetic shards
    # (the real 10B-token corpus is "bring your own data", reference README).
    allow_synthetic: bool = True
    synthetic_tokens_per_shard: int = 2_097_152
    synthetic_num_shards: int = 2


@dataclasses.dataclass(frozen=True)
class TelemetryConfig:
    """Host-side telemetry (mamba_distributed_tpu/obs/): spans, divergence
    sentinels, flight recorder.  Everything defaulting to on is strictly
    host-side and free of device syncs; ``overflow_threshold`` is the one
    knob that changes the compiled train step (docs/OBSERVABILITY.md)."""

    # span tracer -> {log_dir}/events.jsonl (trainer, eval, checkpointing)
    spans: bool = False
    # non-finite loss/grad-norm watchdog on already-fetched host scalars,
    # feeding the flight-recorder ring that dumps on crash/divergence
    sentinel: bool = True
    # raise DivergenceError on a non-finite step (after dumping) — a NaN
    # run only burns compute; opt out for loss-spike research
    halt_on_divergence: bool = True
    flight_recorder_len: int = 64
    # > 0: the compiled train step also returns an int32 flag for
    # grad_norm > threshold (or non-finite), accumulated host-side —
    # the on-device global-norm overflow counter.  0 disables.
    overflow_threshold: float = 0.0
    # --- serving SLO targets (obs/slo.py): rolling-window p95 targets
    # in milliseconds over the last `slo_window_requests` finished
    # requests; 0 leaves a metric untargeted.  Crossing a target emits
    # one `slo_breach` event record (and `slo_recovered` on the way
    # back); scripts/obs_report.py renders the attainment table.  All
    # host-side — no device syncs, no extra jit traces. ---
    slo_ttft_p95_ms: float = 0.0
    slo_itl_p95_ms: float = 0.0
    slo_queue_wait_p95_ms: float = 0.0
    slo_window_requests: int = 64
    # --- live telemetry plane (docs/OBSERVABILITY.md "Live telemetry
    # plane") ---
    # byte cap on a SpanTracer's jsonl file: exceeding it rolls the
    # file to `<name>.1` (one generation kept; obs/export.load_jsonl
    # reads the pair oldest-first).  0 = never rotate.
    span_rotate_bytes: int = 0
    # XLA compile watchdog (obs/watchdog.py): count/time every backend
    # compile, stamp `compiles`/`compile_ms` on serving_tick records
    # and expose them on GET /metrics.  Off (default) keeps records
    # byte-stable.
    compile_watchdog: bool = False
    # > threshold compiles inside one tumbling window fires ONE
    # `compile_thrash` event record (0 = count only, never fire)
    compile_thrash_threshold: int = 0
    compile_thrash_window_s: float = 60.0
    # --- tick-latency regression sentinel (obs/slo.py
    # TickRegressionDetector): breach when the EWMA-smoothed tick
    # latency exceeds `tick_regression_factor` x the learned baseline.
    # factor 0 (default) = off. ---
    tick_regression_factor: float = 0.0
    tick_ewma_alpha: float = 0.1
    tick_regression_warmup: int = 32

    def __post_init__(self):
        if self.flight_recorder_len < 1:
            raise ValueError(
                f"flight_recorder_len must be >= 1, got "
                f"{self.flight_recorder_len}"
            )
        if self.overflow_threshold < 0:
            raise ValueError(
                f"overflow_threshold must be >= 0 (0 disables), got "
                f"{self.overflow_threshold}"
            )
        if self.overflow_threshold > 0 and not self.sentinel:
            raise ValueError(
                "overflow_threshold > 0 needs sentinel=True — the host-"
                "side accumulator and flight record that consume the "
                "on-device flag live on the sentinel"
            )
        for name in ("slo_ttft_p95_ms", "slo_itl_p95_ms",
                     "slo_queue_wait_p95_ms"):
            if getattr(self, name) < 0:
                raise ValueError(
                    f"{name} must be >= 0 (0 disables), got "
                    f"{getattr(self, name)}"
                )
        if self.slo_window_requests < 1:
            raise ValueError(
                f"slo_window_requests must be >= 1, got "
                f"{self.slo_window_requests}"
            )
        if self.span_rotate_bytes < 0:
            raise ValueError(
                f"span_rotate_bytes must be >= 0 (0 = never rotate), "
                f"got {self.span_rotate_bytes}"
            )
        if self.compile_thrash_threshold < 0:
            raise ValueError(
                f"compile_thrash_threshold must be >= 0 (0 = count "
                f"only), got {self.compile_thrash_threshold}"
            )
        if self.compile_thrash_window_s <= 0:
            raise ValueError(
                f"compile_thrash_window_s must be > 0, got "
                f"{self.compile_thrash_window_s}"
            )
        if self.tick_regression_factor and self.tick_regression_factor <= 1:
            raise ValueError(
                f"tick_regression_factor must be > 1 (breach = factor "
                f"x baseline; 0 disables), got "
                f"{self.tick_regression_factor}"
            )
        if not 0.0 < self.tick_ewma_alpha <= 1.0:
            raise ValueError(
                f"tick_ewma_alpha must be in (0, 1], got "
                f"{self.tick_ewma_alpha}"
            )
        if self.tick_regression_warmup < 1:
            raise ValueError(
                f"tick_regression_warmup must be >= 1, got "
                f"{self.tick_regression_warmup}"
            )


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Training loop config (reference: train.py:43-53,89-110,114,133)."""

    model: ModelConfig = dataclasses.field(default_factory=ModelConfig)
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    data: DataConfig = dataclasses.field(default_factory=DataConfig)
    telemetry: TelemetryConfig = dataclasses.field(
        default_factory=TelemetryConfig
    )

    total_batch_size: int = 524288  # tokens/step (train.py:43)
    micro_batch_size: int = 32  # B (train.py:44)
    seq_len: int = 1024  # T (train.py:45)

    max_lr: float = 6e-4
    min_lr_ratio: float = 0.1
    warmup_steps: int = 715
    max_steps: int = 19073
    weight_decay: float = 0.1
    adam_b1: float = 0.9
    adam_b2: float = 0.95
    adam_eps: float = 1e-8
    grad_clip: float = 1.0

    seed: int = 1337  # train.py:37

    val_every: int = 250  # train.py:133
    val_steps: int = 20  # train.py:138
    sample_every: int = 250  # train.py:166
    checkpoint_every: int = 1000  # train.py:152
    log_dir: str = "log"

    # FSDP / remat
    shard_params: bool = False  # shard params+opt state over the fsdp axis
    remat: bool = True  # per-block activation checkpointing

    def __post_init__(self):
        m = self.mesh
        if m.pipe > 1 and (m.seq * m.tensor * m.expert) > 1:
            # the GPipe schedule composes with the pure-DP batch axes
            # (data/fsdp: each replica runs the schedule on its batch
            # slice) but not with seq/tensor/expert, whose shardings cut
            # through the activations the schedule declares stage-local
            raise ValueError(
                f"mesh.pipe={m.pipe} composes with data/fsdp only; got "
                f"seq={m.seq}, tensor={m.tensor}, expert={m.expert}"
            )
        if m.pipe > 1 and self.model.moe_num_experts:
            raise ValueError(
                "MoE models do not pipeline yet (the aux-loss carry is "
                "not threaded through the GPipe schedule); use pipe=1"
            )
        if m.expert > 1:
            if not self.model.moe_num_experts:
                raise ValueError(
                    f"mesh.expert={m.expert} needs a MoE model "
                    "(moe_num_experts > 0)"
                )
            if self.model.moe_num_experts % m.expert:
                raise ValueError(
                    f"moe_num_experts={self.model.moe_num_experts} must "
                    f"divide over mesh.expert={m.expert}"
                )
        if m.pipe > 1 and self.shard_params:
            raise ValueError(
                "mesh.pipe > 1 keeps params replicated across data/fsdp "
                "(stage-sharded over pipe); shard_params=True is not "
                "supported with pipeline parallelism"
            )
        if m.pipe > 1 and self.model.attn_layer_idx:
            # a PERIODIC hybrid pipelines by supersteps (one attn layer per
            # period — models/lm._hybrid_period); aperiodic patterns can't
            # shard evenly over stages
            from mamba_distributed_tpu.models.lm import _hybrid_period

            if _hybrid_period(self.model) is None:
                raise ValueError(
                    "pipeline parallelism needs a uniform layer stack or a "
                    "periodic hybrid (one attn layer every n_layer/n_attn)"
                )
            if len(self.model.attn_layer_idx) % m.pipe != 0:
                raise ValueError(
                    f"hybrid pipeline: n_attn={len(self.model.attn_layer_idx)} "
                    f"supersteps must divide over mesh.pipe={m.pipe} stages"
                )
        elif m.pipe > 1 and self.model.n_layer % m.pipe != 0:
            raise ValueError(
                f"n_layer={self.model.n_layer} must divide over "
                f"mesh.pipe={m.pipe} stages"
            )

    @property
    def grad_accum_steps(self) -> int:
        denom = self.micro_batch_size * self.seq_len * self.data_parallel_size
        assert self.total_batch_size % denom == 0, (
            "make sure total_batch_size is divisible by B * T * dp_size"
        )
        return self.total_batch_size // denom

    @property
    def data_parallel_size(self) -> int:
        # expert is an extra pure-DP batch axis for the non-MoE layers
        return self.mesh.data * self.mesh.fsdp * self.mesh.expert


def _mk(model: Mapping[str, Any], train: Mapping[str, Any]) -> TrainConfig:
    mesh = train.pop("mesh", {})
    data = train.pop("data", {})
    return TrainConfig(
        model=ModelConfig(**dict(model)),
        mesh=MeshConfig(**dict(mesh)),
        data=DataConfig(**dict(data)),
        **dict(train),
    )


# The five BASELINE.json configurations (plus a CPU-runnable smoke preset).
# the published config's multipliers (config.json of Falcon-H1-34B-Instruct)
_FALCON_H1_34B_MULTIPLIERS = dict(
    embedding_multiplier=5.656854249492381,
    lm_head_multiplier=0.0078125,
    ssm_in_multiplier=0.25,
    ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738),
    ssm_out_multiplier=0.08838834764831845,
    attention_in_multiplier=1.0,
    attention_out_multiplier=0.0375,
    key_multiplier=0.011048543456039804,
    mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
)

# the published config's multipliers (config.json of granite-4.0-h-small;
# logits_scaling 16 is the head's 1/16)
_GRANITE_4_H_MULTIPLIERS = dict(
    embedding_multiplier=12.0,
    lm_head_multiplier=0.0625,
    residual_multiplier=0.22,
    attention_multiplier=0.0078125,
)

PRESETS: dict[str, TrainConfig] = {
    # 0. quick-start: minutes on a CPU, for smoke runs and demos
    "mamba2-tiny": _mk(
        dict(d_model=128, n_layer=4, ssm_layer="mamba2", headdim=32,
             d_state=64, chunk_size=64, vocab_size=4096),
        dict(
            seq_len=256,
            micro_batch_size=8,
            total_batch_size=4096,
            max_steps=300,
            warmup_steps=20,
            val_every=25,
        ),
    ),
    # 0c. CPU-runnable hybrid: attention every 2nd layer at tiny scale —
    # the serving/bench shape for the paged-KV hybrid decode path
    "hybrid-tiny": _mk(
        dict(d_model=128, n_layer=4, ssm_layer="mamba2", headdim=32,
             d_state=64, chunk_size=64, vocab_size=4096,
             attn_layer_idx=(1, 3), attn_num_heads=4, attn_num_kv_heads=2,
             prefill_chunk_tokens=128, kv_page_tokens=32,
             kv_slot_tokens=512),
        dict(
            seq_len=256,
            micro_batch_size=8,
            total_batch_size=4096,
            max_steps=300,
            warmup_steps=20,
            val_every=25,
        ),
    ),
    # 0b. CPU-runnable *artifact* scale: the reference's recipe semantics
    # (T=1024, padded GPT-2 vocab, warmup-715 cosine, 250-step val
    # cadence) at a model/batch size a single CPU core can push past the
    # first val checkpoint overnight — used to produce the >=250-step
    # logged curve scored by compare_parity's val@250 check when no chip
    # window allows the full 280M run (ref first checkpoint:
    # /root/reference/log/log_mamba.txt "250 val 5.4865")
    "mamba2-mini": _mk(
        dict(d_model=256, n_layer=8, ssm_layer="mamba2"),
        dict(
            # measured on the round-5 single-core box: ~21 s/step at
            # 4096 tok/step (8192 was 42 s/step — past the overnight
            # budget for 500 steps)
            micro_batch_size=4,
            total_batch_size=4096,
            val_every=250,
        ),
    ),
    # 1. repo default: Mamba-2 280M, seq 1024, single chip
    "mamba2-280m": _mk(
        dict(d_model=768, n_layer=64, ssm_layer="mamba2"),
        dict(),
    ),
    # reference train.py:75 as-written actually builds Mamba-1 (SURVEY 2.4)
    "mamba1-280m": _mk(
        dict(d_model=768, n_layer=64, ssm_layer="mamba1"),
        dict(),
    ),
    # single-chip hybrid (config-5 architecture at 280M scale): attention
    # every 8th layer, GQA 12q/4kv — the shape the attn_impl sweep benches
    "hybrid-280m": _mk(
        dict(d_model=768, n_layer=64, ssm_layer="mamba2",
             attn_layer_idx=tuple(range(3, 64, 8)), attn_num_heads=12,
             attn_num_kv_heads=4),
        dict(),
    ),
    # 2. 280M data-parallel over 8 chips (DDP -> pjit drop-in)
    "mamba2-280m-dp8": _mk(
        dict(d_model=768, n_layer=64, ssm_layer="mamba2"),
        dict(mesh=dict(data=8)),
    ),
    # 3. 1.3B FSDP on 16 chips (param + optimizer-state sharding)
    "mamba2-1.3b-fsdp16": _mk(
        dict(d_model=2048, n_layer=48, ssm_layer="mamba2"),
        dict(
            mesh=dict(fsdp=16),
            shard_params=True,
            micro_batch_size=8,
            total_batch_size=1048576,
        ),
    ),
    # 4. 2.8B long-context: seq 8192, sequence-parallel over 32 chips
    "mamba2-2.8b-sp32": _mk(
        dict(d_model=2560, n_layer=64, ssm_layer="mamba2"),
        dict(
            mesh=dict(fsdp=8, seq=4),
            shard_params=True,
            seq_len=8192,
            micro_batch_size=8,
            total_batch_size=2097152,
        ),
    ),
    # 5. Jamba-style hybrid 7B (attention every 8th layer) on 64 chips
    "hybrid-7b": _mk(
        dict(
            d_model=4096,
            n_layer=32,
            ssm_layer="mamba2",
            d_intermediate=14336,
            attn_layer_idx=tuple(range(3, 32, 8)),
            attn_num_heads=32,
            attn_num_kv_heads=8,
        ),
        dict(
            mesh=dict(fsdp=16, seq=4),
            shard_params=True,
            seq_len=4096,
            micro_batch_size=4,
            total_batch_size=4194304,
        ),
    ),
    # 6. Falcon-H1-34B-Instruct (tiiuae; huggingface.co/tiiuae/
    # Falcon-H1-34B-Instruct config.json) at its published widths, cut in
    # DEPTH alone to one chip's six of the 72 layers: every block runs 32
    # Mamba-2 heads (d_ssm 4096, 2 groups, state 256) and 20/4 GQA heads of
    # 128 on one normed input, then a 21,504-wide SwiGLU; muP multipliers
    # throughout; untied head over 261,120 rows.  A serving preset
    # (benchmark/configs/falcon-h1-34b.json states the cut).
    "falcon-h1-34b": _mk(
        dict(d_model=5120, n_layer=6, vocab_size=261120, ssm_layer="mamba2",
             d_ssm=4096, headdim=128, d_state=256, ngroups=2, chunk_size=128,
             d_intermediate=21504, tie_embeddings=False,
             attn_parallel=True, attn_layer_idx=tuple(range(6)),
             attn_num_heads=20, attn_num_kv_heads=4, attn_head_dim=128,
             rope_theta=1e11, param_dtype="bfloat16",
             **_FALCON_H1_34B_MULTIPLIERS,
             kv_slot_tokens=8192, kv_page_tokens=64,
             prefill_chunk_tokens=512),
        dict(),
    ),
    # its CPU-runnable toy: the same block and the same multipliers at
    # tiny widths (d_ssm stated and != expand * d_model, 2 groups)
    "falcon-h1-tiny": _mk(
        dict(d_model=64, n_layer=2, vocab_size=512, ssm_layer="mamba2",
             d_ssm=96, headdim=16, d_state=32, ngroups=2, chunk_size=32,
             d_intermediate=160, tie_embeddings=False,
             attn_parallel=True, attn_layer_idx=(0, 1),
             attn_num_heads=4, attn_num_kv_heads=2, attn_head_dim=8,
             rope_theta=1e11,
             **_FALCON_H1_34B_MULTIPLIERS,
             kv_slot_tokens=256, kv_page_tokens=16,
             prefill_chunk_tokens=64),
        dict(seq_len=128, micro_batch_size=4, total_batch_size=512),
    ),
    # 7. granite-4.0-h-small (ibm-granite; huggingface.co/ibm-granite/
    # granite-4.0-h-small config.json, "32B-A9B") at its published widths:
    # nine Mamba-2 layers (128 heads of 64, state 128, one group) and one
    # NoPE attention layer (32/8 heads of 128) a period of ten, each
    # followed by 72 routed experts of width 768 (top-10, softmax over the
    # chosen) beside a shared expert of 1,536; four scalar multipliers;
    # tied embedding of 100,352 rows.  Two cuts that go together: ONE
    # period of the 40 layers, and one chip's 36 of each layer's 72 experts
    # (two chips share a layer).  A serving preset
    # (benchmark/configs/granite-4.0-h-small.json states the cuts).
    "granite-4.0-h-small": _mk(
        dict(d_model=4096, n_layer=10, vocab_size=100352, ssm_layer="mamba2",
             headdim=64, d_state=128, ngroups=1, chunk_size=256,
             attn_layer_idx=(5,), attn_num_heads=32, attn_num_kv_heads=8,
             attn_head_dim=128, attn_rotary_dim=0,
             d_intermediate=768, moe_num_experts=72, moe_top_k=10,
             moe_first_expert=0, moe_experts_held=36,
             moe_shared_intermediate=1536, param_dtype="bfloat16",
             **_GRANITE_4_H_MULTIPLIERS,
             kv_slot_tokens=2048, kv_page_tokens=64,
             prefill_chunk_tokens=512),
        dict(),
    ),
    # its CPU-runnable toy: the same block pattern, gate and multipliers at
    # tiny widths, half of eight experts held
    "granite-h-tiny": _mk(
        dict(d_model=64, n_layer=4, vocab_size=512, ssm_layer="mamba2",
             headdim=16, d_state=32, ngroups=1, chunk_size=32,
             attn_layer_idx=(1, 3), attn_num_heads=4, attn_num_kv_heads=2,
             attn_head_dim=16, attn_rotary_dim=0,
             d_intermediate=24, moe_num_experts=8, moe_top_k=3,
             moe_first_expert=0, moe_experts_held=4,
             moe_shared_intermediate=48,
             **_GRANITE_4_H_MULTIPLIERS,
             kv_slot_tokens=256, kv_page_tokens=16,
             prefill_chunk_tokens=64),
        dict(seq_len=128, micro_batch_size=4, total_batch_size=512),
    ),
}


def get_preset(name: str, **overrides: Any) -> TrainConfig:
    cfg = PRESETS[name]
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg
