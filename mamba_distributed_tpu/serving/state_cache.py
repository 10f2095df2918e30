"""Pooled recurrent-state cache: the slot pool under the serving engine.

Mamba's decode state is O(1) per sequence — a (d_conv-1)-wide conv cache
plus one (nheads, headdim, d_state) SSM state per layer — so a serving
"KV cache" collapses to a fixed-capacity pool of S slots whose arrays
never change shape: admitting, advancing, and finishing requests are all
writes into a preallocated batch axis ("Compiler-First State Space
Duality and Portable O(1) Autoregressive Caching for Inference",
PAPERS.md; the slot-pool idiom follows the ragged-paged-attention
serving pattern, minus the paging that attention's growing KV needs).

The pool is a plain pytree:

  pool = {
    "state": {
      "blocks": conv+SSM states, (L, S, ...) leaves  # per-slot rows
      "attn_blocks": (A, P, nkv, page, hd) x2        # hybrid only: the
    },                                # shared HEAD-MAJOR KV page pool
    "logits": (S, V_padded) fp32                    # last logits per slot
    "meta": {
      "active":      (S,) bool   # slot holds a live request
      "done":        (S,) bool   # request finished, awaiting eviction
      "prefilling":  (S,) bool   # slot holds a PARTIAL prefill carry
      "key":         (S, 2) u32  # request base PRNG key
      "step":        (S,) i32    # tokens generated so far
      "max_new":     (S,) i32    # per-request budget
      "top_k":       (S,) i32    # per-slot top-k (<= the engine's static k_max)
      "temperature": (S,) f32
      "eos_id":      (S,) i32    # -1 => no EOS stopping
      "adapter_id":  (S,) i32    # LoRA factor-pool row (0 = none)
    },
  }

``adapter_id`` is the multi-tenant LoRA identity (serving/adapters.py):
the device AdapterCache slot whose stacked factors this slot's rows
multiply inside the tick — 0 (the default, and the only value on
LoRA-less engines) selects the reserved all-zero factor row, an exact
no-op.  It lives in the pool meta — not a separate tick argument — so
a narrow tick's gather and write-back move it with the other axis-0
meta rows for free.

``insert``/``evict`` are jit-compiled with the pool donated: the slot
index is a traced scalar, so admitting a request into ANY slot reuses
one trace, and the update lowers to ``dynamic_update_slice`` on the
donated buffers — no reallocation, no retrace, which is what keeps the
decode loop hot while requests come and go (serving/engine.py).

Chunked prefill (serving/prefill.py) adds partial-prefill residency: a
half-prefilled request occupies its slot with its scan carry —
``stash_prefill`` parks the carry + request meta with
``prefilling=True`` (the decode tick treats the slot as not-live and
must NOT overwrite its state rows: it passes ``~prefilling`` as
``lm_step``'s ``state_mask``, and the update returns those rows
unchanged), ``read_state`` slices the carry
back out to resume at the next budget grant, and ``finish_prefill``
writes the final state + logits and flips ``prefilling`` off, making
the slot decodable.

HYBRID stacks (``attn_layer_idx`` non-empty) pool too: the attention KV
lives in a fixed PAGE pool — per-layer HEAD-MAJOR ``(P, nkv, page, hd)``
page arrays, stacked ``(A, P, nkv, page, hd)`` under
``state["attn_blocks"]`` (page 0 of each layer is a reserved trash
page; head-major is the Pallas kernels' native block layout, so the
decode/prefill page walks read pages without any per-call transpose).
The tick and the chunk step carry the stacked pool whole through their
layer loops and hand it, with a layer index, to the kernels
(models/lm._hybrid_layers): on the hot path it is never sliced by
layer, and with the state donated it is one buffer from a program's
entry to its exit.  The helpers below (``copy_page``, ``read_pages``,
``write_pages``) run between ticks — while the page table and per-slot lengths stay HOST-side on the
engine (they change only between ticks, and the tick takes them as
plain array arguments).  With ``cfg.kv_page_dtype="int8"`` each layer's
tuple grows per-(page, kv-head) f32 scale arrays ``(A, P, nkv)``
alongside the int8 pages (models/attention.py "Int8 KV page
quantization"); every page-granular helper below — ``copy_page``,
``read_pages``, ``write_pages``, the slot-pool shardings — treats the
scales as just more page-axis-1 leaves, so CoW sharing, migration
artifacts and the data-axis tiling carry the scales with their pages
automatically.  ``PagePool`` is the host allocator: admission
reserves ceil((prompt + max_new) / page) pages up front (so a request
can never run out mid-flight), eviction recycles them.  KV HBM is
therefore O(pages in use), not O(capacity * max_len), and slots at
arbitrary positions coexist because everything per-row — RoPE angles,
causal masks, KV write offsets — is computed from the per-slot lengths
(models/attention.py, the ragged/paged-attention pattern).  The state
pytree the jitted slot writes cover is the ``"blocks"`` (conv+SSM)
subtree; attention pages flow through the chunk step's and the tick's
own donations instead.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from mamba_distributed_tpu.config import ModelConfig
from mamba_distributed_tpu.models.lm import init_lm_blocks_state
from mamba_distributed_tpu.obs import scopes


def page_shard_ranges(
    num_pages: int, num_shards: int
) -> list[tuple[int, int]]:
    """Per-shard usable page-id ranges ``[lo, hi)`` mirroring the DEVICE
    layout of a page pool sharded over the data axis: the (P+1)-row
    page arrays (trash page 0 included) partition contiguously, so
    shard d owns rows ``[d*(P+1)/n, (d+1)*(P+1)/n)``, minus row 0 —
    the trash page, which lives in shard 0 and is never handed out.
    Requires ``(num_pages + 1) % num_shards == 0`` (``hybrid_pool_pages``
    rounds the pool up to guarantee it), so host bookkeeping and the
    NamedSharding tile boundaries can never disagree about which shard
    a physical page lives on."""
    rows = num_pages + 1
    if rows % num_shards:
        raise ValueError(
            f"page array of {rows} rows (pages + trash) does not divide "
            f"over {num_shards} shards"
        )
    per = rows // num_shards
    if per < 2:
        # shard 0's tile is the trash page (+ per-2 more): with per == 1
        # it has ZERO usable pages, silently killing every slot resident
        # there — refuse the configuration instead
        raise ValueError(
            f"{num_pages} usable pages over {num_shards} shards leaves "
            f"shard 0 with none (its tile is the trash page); raise "
            f"cfg.kv_pool_pages or lower serving_data_shards"
        )
    return [(max(1, d * per), (d + 1) * per) for d in range(num_shards)]


class PagePoolError(RuntimeError):
    """A page-accounting violation: double free, freeing the trash
    page, or touching a page id outside the pool.  These are always
    caller bugs (the allocator's invariants make them impossible on the
    engine's own paths), so they raise loudly instead of silently
    corrupting the free lists."""


class PagePool:
    """Host-side KV page allocator (hybrid pools): free lists over
    physical pages [1, P) — page 0 is the trash page and never handed
    out.  Purely bookkeeping; the page *arrays* live in the pool pytree
    and are written by the compiled chunk/tick steps.

    Pages are REFCOUNTED: ``alloc`` hands out pages at refcount 1,
    ``incref`` lets another holder (a prefix-cache entry, a slot
    sharing a cached prefix copy-on-write — serving/prefix_cache.py)
    pin the same physical page, and ``free`` decrements — a page
    returns to the free list only when its last holder lets go.  This
    is what lets N slots serve one cached system-prompt's KV from one
    set of physical pages.  ``free`` rejects double-frees and the
    trash page with a named ``PagePoolError``.

    With ``num_shards > 1`` (the mesh-sharded slot pool), the usable
    pages partition into per-shard free lists along the SAME contiguous
    boundaries as the page arrays' NamedSharding over the data axis
    (``page_shard_ranges``): a slot resident in data-shard d allocates
    only from shard d's pages, so every slot's KV reads and writes stay
    on the devices that hold its rows of the pool.  The 2-D serving
    mesh's MODEL axis is invisible here — weights shard over it, pages
    never do (parallel/sharding.serving_param_specs vs
    slot_pool_specs), so this accounting is identical at any
    ``serving_model_shards``."""

    def __init__(self, num_pages: int, num_shards: int = 1):
        if num_pages < 1:
            raise ValueError(f"need >= 1 usable page, got {num_pages}")
        if num_shards < 1:
            raise ValueError(f"need >= 1 shard, got {num_shards}")
        self.num_pages = num_pages
        self.num_shards = num_shards
        self._ranges = page_shard_ranges(num_pages, num_shards)
        self._free_lists = [list(range(lo, hi)) for lo, hi in self._ranges]
        self._refs: dict[int, int] = {}  # allocated page -> holder count

    @property
    def _free(self) -> list[int]:
        """Flat sorted view of every free page (shard-agnostic callers
        and tests; per-shard state lives in ``_free_lists``)."""
        return sorted(p for lst in self._free_lists for p in lst)

    @property
    def free_pages(self) -> int:
        return sum(len(lst) for lst in self._free_lists)

    def free_pages_in(self, shard: int) -> int:
        return len(self._free_lists[shard])

    def shard_capacity(self, shard: int) -> int:
        """Usable pages shard ``shard`` could EVER have free (its range
        size) — the bound the admission deadlock check tests against."""
        lo, hi = self._ranges[shard]
        return hi - lo

    @property
    def pages_in_use(self) -> int:
        return self.num_pages - self.free_pages

    def _owner(self, page: int) -> int:
        if not 1 <= page <= self.num_pages:
            raise ValueError(f"page {page} outside every shard range")
        # ranges are uniform contiguous tiles of the (P+1)-row page axis
        return page // ((self.num_pages + 1) // self.num_shards)

    def alloc(self, n: int, shard: int = 0) -> list[int]:
        """Reserve ``n`` pages from ``shard``'s range, or raise if it
        can't cover them (callers check ``free_pages_in`` first —
        admission just waits).  Pages come back at refcount 1."""
        lst = self._free_lists[shard]
        if n > len(lst):
            raise RuntimeError(
                f"KV page pool exhausted: want {n}, shard {shard} has "
                f"{len(lst)}"
            )
        ids, self._free_lists[shard] = lst[:n], lst[n:]
        for p in ids:
            self._refs[p] = 1
        return ids

    def incref(self, ids: list[int]) -> None:
        """Add one holder to each page (prefix-cache entries pinning a
        cached prefix's KV; a slot admitted onto shared pages).  Only
        allocated pages can gain holders."""
        for p in ids:
            if self._refs.get(p, 0) <= 0:
                raise PagePoolError(
                    f"incref of page {p}, which is not allocated — only a "
                    f"live page can gain a holder"
                )
        for p in ids:
            self._refs[p] += 1

    def refcount(self, page: int) -> int:
        """Current holder count (0 = free / never allocated)."""
        return self._refs.get(page, 0)

    def free(self, ids: list[int]) -> None:
        """Drop one holder per page; a page returns to its shard's free
        list only at refcount 0 (eviction decrefs, never yanks a page a
        prefix-cache entry or a sharing slot still reads).  Raises
        ``PagePoolError`` on the trash page, on ids outside the pool,
        and on double-frees (including a duplicate id inside one batch)
        — all caller bugs."""
        touched = set()
        for p in ids:
            if p == 0:
                raise PagePoolError(
                    "page 0 is the trash page — it is never allocated and "
                    "must never be freed (masked writes depend on it)"
                )
            if not 1 <= p <= self.num_pages:
                raise PagePoolError(
                    f"page {p} is outside the pool's [1, {self.num_pages}] "
                    f"physical range"
                )
            rc = self._refs.get(p, 0)
            if rc <= 0:
                raise PagePoolError(
                    f"double free of page {p}: it has no holders (already "
                    f"on the free list or never allocated)"
                )
            if rc == 1:
                del self._refs[p]
                d = self._owner(p)
                self._free_lists[d].append(p)
                touched.add(d)
            else:
                self._refs[p] = rc - 1
        for d in touched:
            self._free_lists[d].sort()  # deterministic reuse order


def hybrid_pool_pages(
    cfg: ModelConfig, capacity: int, num_shards: int = 1
) -> int:
    """Usable page count of a serving pool (excluding the trash page):
    ``cfg.kv_pool_pages``, or auto = every slot can run to its full
    ``kv_slot_tokens`` budget simultaneously.  With a sharded pool the
    count rounds UP so the page arrays' (P+1)-row page axis divides
    evenly over the data axis — NamedSharding can't place uneven tiles,
    and the extra pages are usable capacity, never waste."""
    pages = cfg.kv_pool_pages or capacity * cfg.kv_pages_per_slot
    if num_shards > 1 and (pages + 1) % num_shards:
        pages += num_shards - (pages + 1) % num_shards
    return pages


def init_pool(cfg: ModelConfig, capacity: int, num_shards: int = 1) -> dict:
    """Allocate an empty slot pool for ``capacity`` concurrent requests.

    ``num_shards`` sizes a hybrid pool's page count for a mesh-sharded
    batch axis (``hybrid_pool_pages`` rounding) — the pytree itself is
    layout-agnostic; the engine device_puts it with
    ``parallel/sharding.slot_pool_shardings``."""
    if capacity < 1:
        raise ValueError(f"capacity must be >= 1, got {capacity}")
    S = capacity
    state = {"blocks": init_lm_blocks_state(cfg, batch=S)}
    if cfg.attn_layer_idx:
        if cfg.effective_prefill_chunk_tokens <= 0:
            raise ValueError(
                "hybrid serving needs chunked prefill: every hybrid "
                "prompt runs through the chunk step (the one prefill "
                "that writes straight into the paged KV pool); set "
                "prefill_chunk_tokens > 0"
            )
        from mamba_distributed_tpu.models.attention import (
            init_attention_state,
        )

        n_pages = hybrid_pool_pages(cfg, capacity, num_shards)
        # init_attention_state builds (1 + batch*W) pages; ask for the
        # pool's page count directly via batch=n_pages, W=1-page slots
        pages = [
            init_attention_state(cfg, n_pages, cfg.kv_page_tokens)
            for _ in cfg.attn_layer_idx
        ]
        state["attn_blocks"] = jax.tree.map(
            lambda *xs: jnp.stack(xs), *pages
        )
    return {
        "state": state,
        "logits": jnp.zeros((S, cfg.vocab_size_padded), jnp.float32),
        "meta": {
            "active": jnp.zeros((S,), bool),
            "done": jnp.zeros((S,), bool),
            "prefilling": jnp.zeros((S,), bool),
            "key": jnp.zeros((S, 2), jnp.uint32),
            "step": jnp.zeros((S,), jnp.int32),
            "max_new": jnp.ones((S,), jnp.int32),
            "top_k": jnp.ones((S,), jnp.int32),
            "temperature": jnp.ones((S,), jnp.float32),
            "eos_id": jnp.full((S,), -1, jnp.int32),
            "adapter_id": jnp.zeros((S,), jnp.int32),
        },
    }


def _set_row(arr: jax.Array, slot: jax.Array, value) -> jax.Array:
    """Write one row of a (S, ...) array at a traced slot index."""
    v = jnp.asarray(value, arr.dtype).reshape((1,) + arr.shape[1:])
    return jax.lax.dynamic_update_slice_in_dim(arr, v, slot, axis=0)


@functools.partial(jax.jit, donate_argnums=(0,))
@jax.named_scope(scopes.POOL_SELECT)
def insert(
    pool: dict,
    slot: jax.Array,
    state: dict,
    logits: jax.Array,
    key: jax.Array,
    max_new: jax.Array,
    top_k: jax.Array,
    temperature: jax.Array,
    eos_id: jax.Array,
    adapter_id: jax.Array = 0,
) -> dict:
    """Admit a prefilled request (batch-1 ``state`` + last ``logits``)
    into ``slot``.  One trace serves every (slot, request) combination —
    all arguments are traced, the pool buffers are donated.
    ``adapter_id`` is the request's LoRA factor-pool row (0 = none)."""
    # state leaves are layer-stacked (L, 1, ...) -> write batch axis 1
    new_state = _write_blocks(pool["state"], slot, state)
    meta = pool["meta"]
    new_meta = {
        "active": _set_row(meta["active"], slot, True),
        "done": _set_row(meta["done"], slot, False),
        "prefilling": _set_row(meta["prefilling"], slot, False),
        "key": _set_row(meta["key"], slot, key),
        "step": _set_row(meta["step"], slot, 0),
        "max_new": _set_row(meta["max_new"], slot, max_new),
        "top_k": _set_row(meta["top_k"], slot, top_k),
        "temperature": _set_row(meta["temperature"], slot, temperature),
        "eos_id": _set_row(meta["eos_id"], slot, eos_id),
        "adapter_id": _set_row(meta["adapter_id"], slot, adapter_id),
    }
    return {
        "state": new_state,
        "logits": _set_row(pool["logits"], slot, logits),
        "meta": new_meta,
    }


@functools.partial(jax.jit, donate_argnums=(0,))
def restore(
    pool: dict,
    slot: jax.Array,
    state: dict,
    logits: jax.Array,
    key: jax.Array,
    step: jax.Array,
    max_new: jax.Array,
    top_k: jax.Array,
    temperature: jax.Array,
    eos_id: jax.Array,
    adapter_id: jax.Array = 0,
) -> dict:
    """Re-admit a PREEMPTED request mid-decode: identical to ``insert``
    except the generated-token counter is restored instead of zeroed,
    so the next tick samples ``fold_in(key, step)`` — the stream
    continues bit-exactly where the swap-out cut it (the engine's
    priority-preemption path, serving/engine.py).  ``adapter_id`` is
    re-stamped from the tracker (the factor-pool row may differ on a
    migration target — cache slots are engine-local)."""
    new_state = _write_blocks(pool["state"], slot, state)
    meta = pool["meta"]
    new_meta = {
        "active": _set_row(meta["active"], slot, True),
        "done": _set_row(meta["done"], slot, False),
        "prefilling": _set_row(meta["prefilling"], slot, False),
        "key": _set_row(meta["key"], slot, key),
        "step": _set_row(meta["step"], slot, step),
        "max_new": _set_row(meta["max_new"], slot, max_new),
        "top_k": _set_row(meta["top_k"], slot, top_k),
        "temperature": _set_row(meta["temperature"], slot, temperature),
        "eos_id": _set_row(meta["eos_id"], slot, eos_id),
        "adapter_id": _set_row(meta["adapter_id"], slot, adapter_id),
    }
    return {
        "state": new_state,
        "logits": _set_row(pool["logits"], slot, logits),
        "meta": new_meta,
    }


@functools.partial(jax.jit, donate_argnums=(0,))
def copy_page(attn_blocks, src: jax.Array, dst: jax.Array):
    """Copy-on-write page duplication: copy physical page ``src`` into
    ``dst`` across every attention layer's K and V pool (the page axis
    is axis 1 of the (A, P+1, nkv, page, hd) leaves), in place on the
    donated buffers.  The prefix cache uses it so a slot that APPENDS
    to a shared cached prefix writes into its own copy of the boundary
    page — sharers keep reading the frozen original.  One trace serves
    every (src, dst) pair (both indices are traced scalars)."""

    def cp(p):
        page = jax.lax.dynamic_slice_in_dim(p, src, 1, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(p, page, dst, axis=1)

    return jax.tree.map(cp, attn_blocks)


@jax.jit
def read_pages(attn_blocks, page_ids: jax.Array):
    """Gather physical pages ``page_ids`` (n,) out of every attention
    layer's K and V pool (page axis 1 of the (A, P+1, nkv, page, hd)
    leaves) -> (A, n, nkv, page, hd) leaves, logical order.  The
    serialization half of the disaggregated prefill->decode MIGRATION
    artifact (serving/engine._package_migration): the prefill replica
    reads the request's live pages here and ``jax.device_get``s them
    alongside the O(1) conv/SSM carry.  NOT donated — the source pool
    lives on; ``page_ids`` is traced, so one trace serves every page
    set of a given (pow2-bucketed) count."""
    return jax.tree.map(
        lambda p: jnp.take(p, page_ids, axis=1), attn_blocks
    )


@functools.partial(jax.jit, donate_argnums=(0,))
def write_pages(attn_blocks, data, page_ids: jax.Array):
    """Scatter serialized page ``data`` (read_pages layout) into
    physical pages ``page_ids`` of the donated pool — the restore half
    of the migration artifact, run on the DECODE replica against its
    own freshly allocated page ids.  ``page_ids`` is traced (one trace
    per bucketed count); pad entries may point at the trash page 0,
    whose contents are garbage by contract (masked writes land there),
    so bucket padding never corrupts a live page."""
    return jax.tree.map(
        lambda p, d: p.at[:, page_ids].set(d.astype(p.dtype)),
        attn_blocks, data,
    )


def _write_blocks(pool_state, slot: jax.Array, state):
    """Write a batch-1 ``{"blocks": ...}`` pytree into ``slot`` of the
    (L, S, ...) conv+SSM pool leaves (shared by insert / stash_prefill /
    finish_prefill).  Only the "blocks" subtree has a per-slot batch
    axis — hybrid attention KV lives in the shared page pool and is
    written by the chunk/tick steps themselves, so any attn entries on
    ``pool_state`` pass through untouched (and ``state`` must not carry
    them: the engine strips to the blocks subtree before these calls,
    which also keeps the donated page buffers from aliasing another
    argument)."""
    new_blocks = jax.tree.map(
        lambda p, n: jax.lax.dynamic_update_slice_in_dim(
            p, n.astype(p.dtype), slot, axis=1
        ),
        pool_state["blocks"],
        state["blocks"],
    )
    return {**pool_state, "blocks": new_blocks}


# ------------------------------------------------- the tick's lane ladder
#
# The decode tick launches the narrowest rung of a fixed ladder that holds
# the decodable slots (serving/engine.py; docs/SERVING.md "Occupancy-
# adaptive ticks"): the live slots' rows are gathered into W lanes, the
# sub-steps run at lane width, and the advanced rows are written back into
# the donated pool.  ``gather_rows`` / ``scatter_rows`` are that device
# side, traced inside the jitted ``_tick``; ``gather_slots`` /
# ``scatter_slots`` are the same two as programs of their own, for the
# speculative tick, whose verify and commit are separate launches.  One
# trace a rung (the lane maps are traced; only the width is a shape).
TRACE_COUNTS = {"gather": 0, "scatter": 0}


def _constrain_rows(rows: dict, mesh):
    """Pin a ``{"blocks", "logits", "meta"}`` tree to the data-axis layout
    of the full pool (the SAME ``slot_pool_specs`` rules: the engine keeps a
    rung a multiple of the shard count and gathers shard-locally, so the
    tiling carries over)."""
    if mesh is None:
        return rows
    from mamba_distributed_tpu.parallel.sharding import slot_pool_shardings

    return jax.lax.with_sharding_constraint(
        rows, slot_pool_shardings(rows, mesh)
    )


@jax.named_scope(scopes.POOL_SELECT)
def gather_rows(rows: dict, idx: jax.Array, keep: jax.Array, mesh=None):
    """Gather slot rows ``idx`` (W,) of a ``{"blocks", "logits", "meta"}``
    tree (the per-slot subtrees of a pool: ``blocks`` leaves (L, S, ...)
    take axis 1, ``logits``/``meta`` leaves axis 0) into a compact
    (.., W, ..) tree.  ``keep`` (W,) marks the lanes that carry a slot: a
    pad lane repeats an in-range row of its shard, is made inactive here (it
    samples nothing and, in a hybrid, writes its KV row to the trash page)
    and is dropped by ``scatter_rows``."""
    # mode="clip": the default ("fill") would add a select over the lanes
    take = functools.partial(jnp.take, indices=idx, mode="clip")
    out = {
        "blocks": jax.tree.map(lambda a: take(a, axis=1), rows["blocks"]),
        "logits": take(rows["logits"], axis=0),
        "meta": jax.tree.map(lambda a: take(a, axis=0), rows["meta"]),
    }
    out["meta"]["active"] = out["meta"]["active"] & keep
    return _constrain_rows(out, mesh)


@jax.named_scope(scopes.POOL_SELECT)
def scatter_rows(rows: dict, compact: dict, idx: jax.Array,
                 keep: jax.Array, mesh=None):
    """Write a narrow launch's lanes back into the full-width rows, in
    place: lane j goes to slot ``idx[j]`` where ``keep[j]``, one
    ``dynamic_update_slice`` a lane and leaf on the donated buffers (what
    ``_write_blocks`` does for one slot), and no select or copy over them.
    The loop runs over the kept lanes alone, so a pad lane writes nothing
    and a slot no lane names (empty, mid-prefill) keeps its bits."""
    kept_first = jnp.argsort(~keep, stable=True)

    def write(j, rows):
        lane = kept_first[j]
        slot = idx[lane]

        def put(axis):
            def one(full, lanes):
                row = jax.lax.dynamic_slice_in_dim(lanes, lane, 1, axis)
                return jax.lax.dynamic_update_slice_in_dim(
                    full, row.astype(full.dtype), slot, axis)
            return one

        return {
            "blocks": jax.tree.map(put(1), rows["blocks"],
                                   compact["blocks"]),
            "logits": put(0)(rows["logits"], compact["logits"]),
            "meta": jax.tree.map(put(0), rows["meta"], compact["meta"]),
        }

    out = jax.lax.fori_loop(0, keep.sum(), write, rows)
    return _constrain_rows(out, mesh)


@functools.partial(jax.jit, static_argnames=("mesh",))
def gather_slots(rows: dict, idx: jax.Array, keep: jax.Array, mesh=None):
    """``gather_rows`` as a program (the speculative tick).  NOT donated:
    the full pool lives on, and ``scatter_slots`` writes into it."""
    TRACE_COUNTS["gather"] += 1
    return gather_rows(rows, idx, keep, mesh)


@functools.partial(jax.jit, static_argnames=("mesh",), donate_argnums=(0,))
def scatter_slots(rows: dict, compact: dict, idx: jax.Array,
                  keep: jax.Array, mesh=None):
    """``scatter_rows`` as a program (the speculative tick).  ``rows`` is
    donated and the output aliases it; the compact buffers are the launch's
    spent output and expire."""
    TRACE_COUNTS["scatter"] += 1
    return scatter_rows(rows, compact, idx, keep, mesh)


@jax.jit
def idle_meta(meta: dict) -> dict:
    """A pool's meta with every slot parked: nothing live, every row held.
    The engine hands it to a full-width launch that has to run and change
    nothing (the ladder's warm-up), and puts the real meta back after.
    Every leaf is a buffer of its own: the launch donates what it is given,
    and the real meta must outlive it."""
    return {
        **jax.tree.map(jnp.copy, meta),
        "active": jnp.zeros_like(meta["active"]),
        "done": jnp.zeros_like(meta["done"]),
        "prefilling": jnp.ones_like(meta["prefilling"]),
    }


@functools.partial(jax.jit, donate_argnums=(0,))
@jax.named_scope(scopes.POOL_SELECT)
def evict(pool: dict, slot: jax.Array) -> dict:
    """Free ``slot``: mark it empty.  The stale state/logits stay in
    place — the next ``insert`` overwrites them, and the decode tick
    masks inactive slots, so no scrubbing is needed."""
    meta = dict(pool["meta"])
    meta["active"] = _set_row(meta["active"], slot, False)
    meta["done"] = _set_row(meta["done"], slot, False)
    meta["prefilling"] = _set_row(meta["prefilling"], slot, False)
    return {"state": pool["state"], "logits": pool["logits"], "meta": meta}


# ------------------------------------------------- partial-prefill residency


@functools.partial(jax.jit, donate_argnums=(0,))
@jax.named_scope(scopes.POOL_SELECT)
def stash_prefill(
    pool: dict,
    slot: jax.Array,
    state: dict,
    key: jax.Array,
    max_new: jax.Array,
    top_k: jax.Array,
    temperature: jax.Array,
    eos_id: jax.Array,
    adapter_id: jax.Array = 0,
) -> dict:
    """Park a PARTIAL prefill carry in ``slot``: the request occupies the
    slot (``active=True``) with its chunk-scan carry and its sampling
    meta, but ``prefilling=True`` keeps it out of the decode tick — the
    tick masks it from sampling AND from state writes (a tick's
    ``lm_step`` over the whole pool must not clobber the carry:
    ``state_mask``).  The
    slot's stale logits are left in place (masked; ``finish_prefill``
    writes the real ones).  Idempotent — re-stashing after more chunks
    just overwrites the carry."""
    meta = pool["meta"]
    new_meta = {
        "active": _set_row(meta["active"], slot, True),
        "done": _set_row(meta["done"], slot, False),
        "prefilling": _set_row(meta["prefilling"], slot, True),
        "key": _set_row(meta["key"], slot, key),
        "step": _set_row(meta["step"], slot, 0),
        "max_new": _set_row(meta["max_new"], slot, max_new),
        "top_k": _set_row(meta["top_k"], slot, top_k),
        "temperature": _set_row(meta["temperature"], slot, temperature),
        "eos_id": _set_row(meta["eos_id"], slot, eos_id),
        "adapter_id": _set_row(meta["adapter_id"], slot, adapter_id),
    }
    return {
        "state": _write_blocks(pool["state"], slot, state),
        "logits": pool["logits"],
        "meta": new_meta,
    }


@jax.jit
@jax.named_scope(scopes.POOL_SELECT)
def read_state(pool: dict, slot: jax.Array):
    """Slice ``slot``'s batch-1 state pytree back out (resume a stashed
    prefill at the next budget grant).  NOT donated — the pool lives on."""
    return {
        "blocks": jax.tree.map(
            lambda p: jax.lax.dynamic_slice_in_dim(p, slot, 1, axis=1),
            pool["state"]["blocks"],
        )
    }


@functools.partial(jax.jit, donate_argnums=(0,))
@jax.named_scope(scopes.POOL_SELECT)
def finish_prefill(pool: dict, slot: jax.Array, state: dict,
                   logits: jax.Array) -> dict:
    """Complete a chunked prefill: write the final carry + last logits and
    flip ``prefilling`` off — the next tick samples this slot's first
    token from ``fold_in(key, step=0)``, exactly like a fresh insert."""
    meta = dict(pool["meta"])
    meta["prefilling"] = _set_row(meta["prefilling"], slot, False)
    return {
        "state": _write_blocks(pool["state"], slot, state),
        "logits": _set_row(pool["logits"], slot, logits),
        "meta": meta,
    }
