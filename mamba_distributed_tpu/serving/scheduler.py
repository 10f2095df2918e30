"""Request lifecycle + FCFS admission for the serving engine.

A request moves QUEUED -> PREFILL -> DECODE -> FINISHED:

  QUEUED    in the scheduler's FCFS queue, waiting for a free slot
  PREFILL   building its recurrent state: one bucketed forward for short
            prompts, or chunk-by-chunk across ticks for long ones
            (serving/prefill.py) — the slot holds the partial carry
  DECODE    occupying a slot; one token per engine tick
  FINISHED  sampled its ``eos_id`` or exhausted ``max_new_tokens``

The scheduler is deliberately minimal — an arrival-order deque plus the
lifecycle bookkeeping.  Admission happens between compiled decode ticks
(serving/engine.py), so policy changes (priorities, prefill batching,
preemption) are host-side swaps that never touch compiled code.
"""

from __future__ import annotations

import dataclasses
import enum
import time
from collections import deque
from typing import Iterator

import jax
import numpy as np

from mamba_distributed_tpu.obs.context import mint_trace_id
from mamba_distributed_tpu.serving.adapters import split_adapter_version


class TenantQuotaExceeded(RuntimeError):
    """Admitting this request would give one tenant (adapter BASE name
    — versions share the quota) more concurrent resident slots than
    ``cfg.tenant_max_slots`` allows.  The engine treats it exactly like
    a KV-page stall: requeue and retry next step — fairness is
    BACKPRESSURE, never shedding (the request stays queued until a
    sibling stream finishes).  ``tenant_max_slots=0`` (default)
    disables the check entirely."""


def check_tenant_quota(adapter: str | None, resident_adapters,
                       max_slots: int) -> None:
    """Raise the named :class:`TenantQuotaExceeded` when ``adapter``
    already holds ``max_slots`` resident slots.  ``resident_adapters``
    is the engine's view of adapter names currently occupying slots
    (None entries = base-model streams, never counted); versioned names
    (``tenant@v2``) count against their base — a tenant cannot dodge
    its quota by shipping a new version."""
    if max_slots <= 0 or not adapter:
        return
    base, _ = split_adapter_version(adapter)
    held = sum(1 for a in resident_adapters
               if a and split_adapter_version(a)[0] == base)
    if held >= max_slots:
        raise TenantQuotaExceeded(
            f"tenant {base!r} holds {held}/{max_slots} resident slots "
            f"(cfg.tenant_max_slots) — request stays queued until one "
            f"frees"
        )


class RequestStatus(enum.Enum):
    QUEUED = "queued"
    PREFILL = "prefill"
    DECODE = "decode"
    FINISHED = "finished"


@dataclasses.dataclass
class GenerationRequest:
    """One generation job.  ``seed`` derives the sampling key; passing the
    same key to a solo ``generate()`` call reproduces this request's
    tokens exactly (the engine parity contract, tests/test_serving.py)."""

    prompt_ids: np.ndarray  # (t,) int32
    max_new_tokens: int = 32
    top_k: int = 50
    temperature: float = 1.0
    eos_id: int | None = None
    seed: int = 0
    key: jax.Array | None = None  # overrides seed when given
    # echo of the id the scheduler assigned at the LAST submit of this
    # object (the authoritative id lives on the scheduler's tracker, so
    # resubmission is safe); submit()/TokenEvents carry the real one
    request_id: int | None = None
    # fabric-wide trace id (obs/context.py).  None => the scheduler
    # mints a fresh one per submit; the ROUTER sets it at placement so
    # a failover re-placement continues the SAME trace — one request,
    # one flow chain in the exported timeline, however many replicas
    # it visited.
    trace_id: str | None = None
    # priority class (higher = more important; None takes
    # cfg.serving_default_priority).  Admission pops the highest
    # priority first (FCFS within a class), and the engine PREEMPTS a
    # lower-priority decoding slot — carry swapped to host RAM,
    # resumed later without re-prefill — when a higher-priority
    # request is stuck queued with no free slot (serving/engine.py).
    priority: int | None = None
    # named LoRA adapter this request decodes under (serving/
    # adapters.py; None = the base model).  Validated at submit against
    # the engine's AdapterRegistry — an unknown name raises the named
    # UnknownAdapterError, never a hang — and carried through the
    # service wire, failover replay, SSE resume and tier migration
    # (the target engine re-pins the factors from its own cache).
    adapter: str | None = None
    # admission deadline (serving/autoscale/admission.py): the longest
    # queue wait this request tolerates, in milliseconds — a fabric
    # with an AdmissionController sheds the request FAST (the named
    # AdmissionRejected; HTTP 429 on the service) when the estimated
    # wait exceeds it.  None defers to the fabric's default deadline
    # (which may itself be off); the plain engine path never reads it,
    # so carrying one is byte-stable without admission control.
    queue_deadline_ms: float | None = None

    def resolve_key(self) -> jax.Array:
        key = self.key if self.key is not None else jax.random.PRNGKey(self.seed)
        if jax.dtypes.issubdtype(key.dtype, jax.dtypes.prng_key):
            # new-style typed keys: unwrap to the raw uint32 pair the slot
            # pool stores (fold_in over raw data draws the same bits)
            key = jax.random.key_data(key)
        return key


@dataclasses.dataclass
class TokenEvent:
    """One streamed token (serve()/step() output, in emission order)."""

    request_id: int
    token: int
    index: int  # 0-based position within the generated suffix
    done: bool
    finish_reason: str | None = None  # "eos" | "length" when done


@dataclasses.dataclass
class GenerationResult:
    request_id: int
    prompt_ids: np.ndarray
    new_tokens: np.ndarray  # generated suffix (includes eos when hit)
    finish_reason: str  # "eos" | "length"

    @property
    def tokens(self) -> np.ndarray:
        """prompt + generated suffix, ``generate()``-shaped."""
        return np.concatenate([self.prompt_ids, self.new_tokens])


@dataclasses.dataclass
class _Tracked:
    """Host-side mirror of one in-flight request.  ``request_id`` lives
    here (not on the GenerationRequest) so submitting the same request
    object twice yields two independent streams."""

    request: GenerationRequest
    request_id: int = -1
    # the trace id every span/record of this request's journey carries
    # (request.trace_id when the router propagated one, else minted at
    # submit — see GenerationRequest.trace_id)
    trace_id: str = ""
    status: RequestStatus = RequestStatus.QUEUED
    slot: int | None = None
    new_tokens: list[int] = dataclasses.field(default_factory=list)
    finish_reason: str | None = None
    # --- host-side lifecycle stamps (time.perf_counter seconds) from
    # which the engine derives queue-wait, TTFT and inter-token latency
    # (obs/: per-request serving telemetry; docs/OBSERVABILITY.md) ---
    t_submit: float = 0.0  # stamped by FCFSScheduler.submit
    t_admit: float | None = None  # slot granted, prefill dispatched
    # the request's LAST prefill program dispatched (the one-shot prefill,
    # or the last chunk grant): between t_admit and here the request waits
    # in the chunk queue, from here to t_first_token for the tick
    t_prefill_done: float | None = None
    t_first_token: float | None = None  # first decode token on host
    t_last_token: float | None = None  # most recent token on host
    # per-request ITL histogram (StreamingHistogram), created at admit;
    # rides in the request's jsonl record so obs_report.py can merge
    # per-token percentiles across requests without storing samples
    itl_hist: object | None = None
    # --- chunked-prefill progress (serving/prefill.py): the plan this
    # request's prompt splits into (None => one-shot path), how many
    # chunks have run, and the accumulated host dispatch time ---
    plan: object | None = None
    chunks_done: int = 0
    prefill_dt: float = 0.0
    # real prompt tokens a partial prefix-cache hit seeded (skipped
    # chunks) — record_prefill at completion reports only the COMPUTED
    # tokens, so prefill throughput never double-counts what
    # prefix_saved_tokens already claims was skipped
    prefill_seeded_tokens: int = 0
    # consecutive chunk grants this slot was passed over for (the SRPT
    # starvation guard, serving/engine._pick_prefill_slot)
    prefill_skipped: int = 0
    # hybrid paged KV: physical page ids this request holds a ref on
    # (reserved at admission, or shared from a cached prefix and
    # incref'd), decref'd on evict/failure (serving/engine.py page
    # allocator; state_cache.PagePool refcounts)
    pages: list | None = None
    # resolved priority class (request.priority, else the scheduler's
    # default) — admission order + preemption rank
    priority: int = 0
    # preemption swap-out state (serving/engine._preempt): host copies
    # of the slot's carry/logits + the generated-token count, so
    # re-admission restores mid-decode without re-prefill.  Survives
    # requeue — clearing it would silently re-prefill and REPLAY
    # already-delivered tokens.
    snapshot: dict | None = None
    preempted: int = 0  # times this request was swapped out
    # prefix-cache outcome at admission: "full" | "partial" | None
    # (miss / cache off) — stamps the request record + TTFT split
    cache_hit: str | None = None
    # --- disaggregated prefill/decode migration (serving/router.py,
    # serving/engine.py).  no_migrate marks a request the migration
    # hook must skip: it already arrived here VIA migration (or the
    # hook declined once — mixed-mode fallback decodes it locally), so
    # re-offering it every step would ping-pong between tiers.
    no_migrate: bool = False
    migrations: int = 0  # prefill->decode handoffs this request took
    migration_ms: float = 0.0  # host time spent packaging + restoring
    migration_source: int | None = None  # replica id that prefilled
    # --- speculative decoding (serving/spec_decode.py, the pending-
    # token scheme): tokens committed to the stream but not yet folded
    # into the device state, how many of them the consumer has already
    # received, and how much committed history the drafter has
    # observed.  All three survive preemption (the snapshot pairs with
    # them) and are reset by requeue() only when the request will
    # re-prefill from scratch.
    spec_pending: list = dataclasses.field(default_factory=list)
    spec_pending_emitted: int = 0
    spec_observed: int = 0
    # --- multi-tenant LoRA (serving/adapters.py): the device factor-
    # pool row this request's slot multiplies (0 = the zero "no
    # adapter" row; None = no cache ref held).  A ref is acquired at
    # admission (like KV pages) and released at finish/failure; it
    # RIDES a preemption snapshot (resume must not re-miss) and is
    # released when the request migrates out (the target re-pins from
    # its own engine-local cache).
    adapter_slot: int | None = None
    # --- mid-stream adapter hot swap (serving/engine.hot_swap_adapter,
    # the PR-15 residual online tuning needed): the request object as
    # the USER submitted it (None until the first swap — finish records
    # and GenerationResult must echo the original prompt/adapter, not
    # the internal continuation request the swap fabricates), the count
    # of tokens already emitted at the LAST swap (``new_tokens`` keeps
    # growing across a swap, but the re-admitted continuation's device
    # step counter restarts at 0 — preempt/park/migration step stamps
    # subtract this base), and how many swaps the stream took (record
    # stamp, absent when zero).
    orig_request: GenerationRequest | None = None
    swap_base: int = 0
    hot_swaps: int = 0


class FCFSScheduler:
    """First-come-first-served admission queue with priority classes:
    ``pop``/``peek`` take the highest-priority entry, FCFS within a
    class — with every request at the default priority this is exactly
    the arrival-order deque it always was."""

    def __init__(self, default_priority: int = 0) -> None:
        self._queue: deque[_Tracked] = deque()
        self._next_id = 0
        self.default_priority = default_priority

    def submit(self, request: GenerationRequest) -> _Tracked:
        prompt = np.asarray(request.prompt_ids, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must hold at least one token")
        if request.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if request.top_k < 1:
            raise ValueError("top_k must be >= 1")
        if request.temperature <= 0.0:
            raise ValueError("temperature must be > 0")
        request.prompt_ids = prompt
        # the scheduler's counter is authoritative: every submit gets a
        # fresh id, so resubmitting an object can't collide two streams
        # (a router-propagated trace_id is deliberately reused though —
        # failover re-placement is the same request's journey)
        tracked = _Tracked(request=request, request_id=self._next_id,
                           trace_id=request.trace_id or mint_trace_id(),
                           priority=(self.default_priority
                                     if request.priority is None
                                     else request.priority),
                           t_submit=time.perf_counter())
        self._next_id += 1
        request.request_id = tracked.request_id  # convenience echo
        self._queue.append(tracked)
        return tracked

    def _best(self) -> int | None:
        """Index of the next request to admit: highest priority,
        earliest arrival (queue position) within a class."""
        if not self._queue:
            return None
        return max(range(len(self._queue)),
                   key=lambda i: (self._queue[i].priority, -i))

    def pop(self) -> _Tracked | None:
        """Next request to admit (priority, then arrival order), or
        None when empty."""
        i = self._best()
        if i is None:
            return None
        tracked = self._queue[i]
        del self._queue[i]
        return tracked

    def peek(self) -> _Tracked | None:
        """What ``pop`` would return, without removing it (the engine's
        preemption check reads the queue's best priority)."""
        i = self._best()
        return None if i is None else self._queue[i]

    def pop_preempted(self) -> _Tracked | None:
        """Next queued PREEMPTED request (one holding a resume
        snapshot), or None.  The engine resumes these even when the
        queue's best request is stalled on KV pages: a swap-in needs no
        pages, and running it is the only way the pages it pins ever
        release (serving/engine._resume_parked).  MIGRATED-in snapshots
        (the disaggregated prefill->decode artifact) are skipped: they
        carry page CONTENTS and re-allocate their full reservation at
        restore, so unlike a preempted swap-in they compete for the
        very pages the stalled head is waiting on."""
        for i, t in enumerate(self._queue):
            if t.snapshot is not None and not t.snapshot.get("migrated"):
                del self._queue[i]
                return t
        return None

    def withdraw_unstarted(self) -> list[_Tracked]:
        """Remove and return every queued request that has NOT started:
        status QUEUED and no resume/migration snapshot.  The drain
        shutdown path (serving/router.drain(requeue_queued=True)) uses
        this to hand queued-but-unplaced work back to the router — a
        draining replica previously stranded its queue unless something
        kept stepping it.  Preempted/migrated entries (snapshot
        holders) stay: their state lives HERE and re-placing them
        elsewhere would either lose it or re-deliver tokens."""
        keep: deque[_Tracked] = deque()
        out: list[_Tracked] = []
        for t in self._queue:
            if t.status is RequestStatus.QUEUED and t.snapshot is None:
                out.append(t)
            else:
                keep.append(t)
        self._queue = keep
        return out

    def requeue(self, tracked: _Tracked) -> None:
        """Put a popped-but-not-admitted request back at the queue head
        (a failed prefill must not drop it; a preempted request resumes
        ahead of its class — it arrived first).  Chunked-prefill
        progress is reset — a prefill retry restarts from chunk 0 with
        a fresh carry — but a preemption ``snapshot`` survives: the
        resume path must restore it, never re-prefill (a re-prefill
        would replay tokens the consumer already has)."""
        tracked.status = RequestStatus.QUEUED
        tracked.slot = None
        tracked.plan = None
        tracked.chunks_done = 0
        tracked.prefill_dt = 0.0
        tracked.prefill_seeded_tokens = 0
        tracked.prefill_skipped = 0
        if tracked.snapshot is None:
            # a re-prefill re-derives the first pending token from the
            # fresh prefill logits; the drafter stream restarts too
            # (spec_observed=0 tells the engine's spec tick to forget
            # it).  A PREEMPTED request keeps all three — its snapshot
            # restores the exact state the pending tokens pair with.
            tracked.spec_pending = []
            tracked.spec_pending_emitted = 0
            tracked.spec_observed = 0
        self._queue.appendleft(tracked)

    @property
    def depth(self) -> int:
        return len(self._queue)

    def __iter__(self) -> Iterator[_Tracked]:
        return iter(self._queue)
