"""Multi-tenant LoRA serving: named adapters, a refcounted device cache,
and the segmented batched-LoRA factor pools one tick launch consumes.

Millions of users means thousands of fine-tuned variants, not one
checkpoint.  Rather than one engine per adapter (N copies of the base
weights, N cold slot pools), ONE engine serves heterogeneous adapters:

  * an :class:`AdapterRegistry` holds up to ``cfg.lora_max_adapters``
    named adapters' low-rank ``{A (d_in, r), B (r, d_out)}`` factors
    over the same ``linear()``-routed projections the serving
    tensor-parallel specs already shard (``_LORA_RULES`` mirrors
    ``parallel/sharding._TP_RULES``: in/out/x projections, attention
    wqkv/out_proj, MLP fc1/fc2 — per LAYER, stacked like the params);
  * an :class:`AdapterCache` generalizes the PagePool refcount/LRU
    discipline to adapter factors: a bounded pool of device slots,
    each holding one adapter's factors stacked into per-target
    ``(L, slots + 1, d_in, r)`` / ``(L, slots + 1, r, d_out)`` arrays
    — ROW 0 is the reserved all-zero "no adapter" entry, the factor
    pools' trash page.  Admission ``acquire``s a slot like it reserves
    KV pages (waits when every slot is pinned — never a mid-flight
    miss), refcounts pin a slot while any resident stream uses it,
    zero-ref residents evict LRU, and a double ``release`` raises the
    named :class:`AdapterCacheError` (the PR-9 page rules, re-applied);
  * the engine attaches the pools under each target's param dict
    (``attach_adapter_pools``) and every compiled launch binds the
    per-row adapter ids from the slot pool's meta
    (``bind_adapter_ids``), so ``models/common.linear`` computes

        y = x @ W + (x @ A[ids]) @ B[ids]

    — slots running DIFFERENT adapters share ONE launch, and id-0 rows
    multiply the zero factors (an exact +0.0 on the fp32 accumulator).

TP composition: a COLUMN-parallel base kernel shards its output axis,
so its ``B`` factor shards ``d_out`` with it (``A`` replicated: the
rank-r inner activation is tiny); a ROW-parallel base kernel shards its
input axis, so ``A`` shards ``d_in`` with it (``B`` replicated; GSPMD
inserts the same all-reduce the base matmul needs).  The rules live in
``parallel/sharding.serving_param_specs`` next to the kernel rules.

Scaling: the conventional LoRA weight ``alpha / rank`` is folded into
the stored ``B`` factors ONCE at registration, so the hot path never
multiplies by it and the merged reference is simply ``W + A @ B_eff``.

Parity regime: a stream under adapter ``a`` must match solo
``generate()`` on the MERGED weights ``merge_adapter_params(params,
registry, a)`` — via ``ops/quant.assert_stream_close``, NOT bit
equality: the segmented delta re-associates float sums (x@(W + AB)
vs x@W + (x@A)@B), so bit-exactness is the wrong pin here; greedy
tokens agree exactly on the fp32 CPU matrix (tests/test_tenant_lora.py
pins zero disagreements across mamba1/mamba2/hybrid, chunked longs,
(2,2) TP, prefix-warm, preempt/resume, migration, spec K>0 and
narrow ticks).

Quantized int8 base weights + a LoRA delta is a ROADMAP residual — the
engine rejects the combination with a named error rather than silently
mixing the two dequant paths.
"""

from __future__ import annotations

import functools
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np


class AdapterError(RuntimeError):
    """Base of the named multi-tenant LoRA errors."""


class UnknownAdapterError(AdapterError, ValueError):
    """A request (or merge/acquire) named an adapter the registry does
    not hold.  ValueError too, so the service wire marks it retriable
    and the HTTP front end can map it to a 404 — never a hang."""


class AdapterCacheError(AdapterError):
    """An adapter-slot accounting violation: double release, releasing
    a never-acquired adapter, or touching the reserved zero row.
    Always a caller bug (the engine's own paths keep the invariants),
    so it raises loudly instead of silently corrupting refcounts —
    the PagePoolError contract, re-applied to factor slots."""


class AdapterVersionError(AdapterError, ValueError):
    """A version-ordering violation on registration: re-registering an
    existing ``name@vN`` or registering a version at or below the
    current latest (a rollback).  Versions are monotone per base name —
    online tuning deploys ``name@v(N+1)``, never rewrites history.
    ValueError too, so the service wire marks it retriable."""


def split_adapter_version(name: str) -> tuple[str, int | None]:
    """``"tenant@v3"`` -> ``("tenant", 3)``; a bare name -> ``(name,
    None)``.  Only a trailing ``@v<digits>`` is version syntax — any
    other ``@`` is part of the tenant identity."""
    base, sep, tail = name.rpartition("@v")
    if sep and base and tail.isdigit():
        return base, int(tail)
    return name, None


def versioned_name(base: str, version: int) -> str:
    """Canonical registry key: v1 is the BARE name (the PR-15
    single-version fast path — byte-identical salts/records/wire when
    only one version ever exists), v2+ are ``base@vN``."""
    return base if version == 1 else f"{base}@v{version}"


# (path-suffix pattern) of the linear()-routed projection dicts that
# accept LoRA factors — the same projections _TP_RULES shards, which is
# what makes the A/B sharding rules compose with tensor parallelism.
# (mamba1's dt_proj bypasses linear(); conv/router/norms/SSM scalars
# are not matmul targets — exactly the ops/quant.py denylist.)
_LORA_RULES: tuple[tuple[str, ...], ...] = (
    ("mixer", "in_proj"),
    ("mixer", "out_proj"),
    ("mixer", "x_proj"),
    ("mixer", "wqkv"),
    ("mlp", "fc1"),
    ("mlp", "fc2"),
)


def is_lora_target(names: list[str]) -> bool:
    """Does the param-dict path accept LoRA factors?"""
    return any(tuple(names[-len(p):]) == p for p in _LORA_RULES)


def lora_targets(params: dict) -> "OrderedDict[str, tuple[int, int, int]]":
    """Derive the adapter target table from a param tree: ordered map
    of ``"a/b/c"`` path -> ``(n_stack, d_in, d_out)`` for every
    layer-stacked projection kernel ``_LORA_RULES`` names.  Factors are
    per LAYER (the leading stack axis mirrors the param layout so the
    scan-over-layers slices them alongside the kernels)."""
    out: OrderedDict[str, tuple[int, int, int]] = OrderedDict()

    def walk(tree, names):
        if not isinstance(tree, dict):
            return
        if "kernel" in tree and not isinstance(tree["kernel"], dict) \
                and is_lora_target(names):
            shape = np.shape(tree["kernel"])
            if len(shape) == 3:  # (L, d_in, d_out) — stacked, as served
                out["/".join(names)] = (shape[0], shape[1], shape[2])
            return
        for k in sorted(tree.keys()):
            walk(tree[k], names + [k])

    walk(params, [])
    if not out:
        raise ValueError(
            "no LoRA-targetable projections found in the param tree "
            "(expected layer-stacked mixer/MLP kernels)"
        )
    return out


def prefix_salt(adapter: str | None) -> bytes:
    """Prefix-cache key salt for one adapter identity.  Carry snapshots
    DEPEND on the adapter whose delta shaped them, so a warm hit under
    adapter X must never seed adapter Y — the engine mixes this into
    every prefix-cache key.  ``None``/empty (no adapter) is ``b""``:
    cache keys byte-identical to a LoRA-less engine's."""
    if not adapter:
        return b""
    return b"adapter:" + adapter.encode("utf-8") + b":"


# ------------------------------------------------------------- registry


class AdapterRegistry:
    """Host-side table of named adapters' fp32 factors.

    Factors are keyed by target path (``lora_targets``); each entry is
    ``{"A": (L, d_in, r) f32, "B": (L, r, d_out) f32}`` with the
    ``alpha / rank`` scale already folded into ``B``.  A registered
    adapter may cover a SUBSET of the targets (LoRA-on-attention-only
    is common); uncovered targets contribute the zero delta.

    One registry may back many engines (the in-process router passes
    one instance through ``engine_kw`` so every replica — including a
    migration target — re-pins factors from the same table); each
    engine keeps its own :class:`AdapterCache` of device slots.
    """

    def __init__(self, cfg, params: dict):
        if cfg.lora_max_adapters <= 0:
            raise ValueError(
                "AdapterRegistry needs cfg.lora_max_adapters > 0 "
                "(0 = multi-tenant LoRA off)"
            )
        self.cfg = cfg
        self.rank = cfg.lora_rank
        self.alpha = cfg.lora_alpha
        self.max_adapters = cfg.lora_max_adapters
        self.targets = lora_targets(params)
        self._adapters: "OrderedDict[str, dict]" = OrderedDict()
        # base name -> highest registered version (monotone; rollbacks
        # raise AdapterVersionError).  v1 is stored under the BARE name.
        self._versions: dict[str, int] = {}

    # ------------------------------------------------------------ lookup

    def __contains__(self, name: str) -> bool:
        return self.resolve(name) in self._adapters

    def __len__(self) -> int:
        return len(self._adapters)

    def names(self) -> list[str]:
        return list(self._adapters.keys())

    def resolve(self, name: str) -> str:
        """Canonical registry key for ``name``: a bare name resolves to
        its LATEST version's key, an explicit ``@v1`` to the bare fast
        path, any other ``@vN`` to itself.  Pure — never raises; an
        unresolvable name passes through and misses in :meth:`factors`
        with the named :class:`UnknownAdapterError`."""
        base, ver = split_adapter_version(name)
        if ver is None:
            cur = self._versions.get(name)
            return name if cur is None else versioned_name(name, cur)
        if ver == 1 and base in self._adapters:
            return base
        return name

    def latest(self, name: str) -> str:
        """The newest registered version of ``name``'s base (version
        syntax on the input is ignored): the deploy target A/B routing
        steers new traffic toward.  Raises the named
        :class:`UnknownAdapterError` on an unknown base."""
        base, _ = split_adapter_version(name)
        cur = self._versions.get(base)
        if cur is None:
            raise UnknownAdapterError(
                f"unknown adapter base {base!r}: this registry holds "
                f"{self.names()}"
            )
        return versioned_name(base, cur)

    def version_of(self, name: str) -> int:
        """The version an adapter name denotes: explicit ``@vN`` -> N,
        bare -> the current latest (1 if only one ever registered)."""
        base, ver = split_adapter_version(name)
        if ver is not None:
            return ver
        return self._versions.get(base, 1)

    def factors(self, name: str) -> dict:
        """The adapter's stored (scaled) factors, keyed by target path.
        Bare names resolve to their latest version.  Raises the named
        :class:`UnknownAdapterError` on a miss."""
        try:
            return self._adapters[self.resolve(name)]
        except KeyError:
            raise UnknownAdapterError(
                f"unknown adapter {name!r}: this registry holds "
                f"{self.names()} (register it, or preload via "
                f"scripts/serve_worker.py --adapter name=path)"
            ) from None

    # ------------------------------------------------------ registration

    def register(self, name: str, factors: dict,
                 alpha: float | None = None) -> str:
        """Register ``factors`` (target path -> {"A", "B"} of UNscaled
        arrays) under ``name``.  Shapes are validated against the
        target table; ``alpha`` (default ``cfg.lora_alpha``) over
        ``rank`` is folded into the stored B once.

        Versioning: a BARE name registers the next version of its base
        (v1 on first sight — stored under the bare key, the PR-15
        single-version fast path; v(N+1) on re-register).  An explicit
        ``name@vN`` pins the version: N at or below the current latest
        raises the named :class:`AdapterVersionError` (history is
        immutable — no overwrites, no rollbacks); forward jumps are
        allowed so a late-joining replica can receive ``@v3`` without
        ever holding v1/v2.  Returns the canonical registered name."""
        if not name:
            raise ValueError("adapter name must be non-empty")
        base, ver = split_adapter_version(name)
        cur = self._versions.get(base, 0)
        if ver is None:
            ver = cur + 1
        elif ver <= cur:
            raise AdapterVersionError(
                f"adapter {base!r} is at v{cur}; registering "
                f"{base}@v{ver} would "
                + ("overwrite it" if ver == cur else "roll it back")
                + " — versions are monotone (register the bare name "
                "for the next version)"
            )
        key = versioned_name(base, ver)
        if len(self._adapters) >= self.max_adapters:
            raise ValueError(
                f"registry full: cfg.lora_max_adapters="
                f"{self.max_adapters} adapters already registered"
            )
        scale = (self.alpha if alpha is None else float(alpha)) / self.rank
        stored: dict[str, dict] = {}
        for path, fac in factors.items():
            if path not in self.targets:
                raise ValueError(
                    f"adapter {name!r} names unknown target {path!r}; "
                    f"valid targets: {list(self.targets)}"
                )
            n, d_in, d_out = self.targets[path]
            A = np.asarray(fac["A"], np.float32)
            B = np.asarray(fac["B"], np.float32)
            if A.shape != (n, d_in, self.rank):
                raise ValueError(
                    f"adapter {name!r} target {path!r}: A shape "
                    f"{A.shape} != {(n, d_in, self.rank)} "
                    f"(cfg.lora_rank={self.rank})"
                )
            if B.shape != (n, self.rank, d_out):
                raise ValueError(
                    f"adapter {name!r} target {path!r}: B shape "
                    f"{B.shape} != {(n, self.rank, d_out)}"
                )
            stored[path] = {"A": A, "B": B * scale}
        if not stored:
            raise ValueError(
                f"adapter {name!r} covers no targets (empty factors)"
            )
        self._adapters[key] = stored
        self._versions[base] = ver
        return key

    def register_random(self, name: str, seed: int = 0,
                        scale: float = 0.05,
                        targets: list[str] | None = None) -> str:
        """Register a random adapter (tests/bench): A ~ N(0, scale/r)
        per target, B ~ N(0, scale) — BOTH nonzero so the delta is
        live from the first token (the conventional B=0 init would
        make every adapter a no-op and parity vacuous)."""
        import zlib

        # crc32, not hash(): str hashing is per-process randomized, and
        # random adapters must be reproducible across worker processes
        rng = np.random.default_rng(
            (zlib.crc32(name.encode("utf-8")) + int(seed)) & 0xFFFFFFFF
        )
        fac = {}
        for path in (targets if targets is not None else self.targets):
            n, d_in, d_out = self.targets[path]
            fac[path] = {
                "A": rng.normal(0.0, scale / self.rank,
                                (n, d_in, self.rank)),
                "B": rng.normal(0.0, scale, (n, self.rank, d_out)),
            }
        return self.register(name, fac)

    # ----------------------------------------------------- merged weights

    def merge(self, params: dict, name: str) -> dict:
        """The PARITY reference: a fresh fp32 master tree with each
        target kernel replaced by ``W + A @ B_eff`` (the scale is
        already inside the stored B).  Feed it to a solo
        ``generate()`` call — its stream is what the engine's
        segmented launch must reproduce per-slot."""
        fac = self.factors(name)

        def walk(tree, names):
            if not isinstance(tree, dict):
                return tree
            path = "/".join(names)
            if path in fac and "kernel" in tree:
                delta = np.einsum(
                    "ndr,nro->ndo", fac[path]["A"], fac[path]["B"]
                )
                kernel = np.asarray(tree["kernel"],
                                    np.float32) + delta
                return {**tree, "kernel": jnp.asarray(kernel)}
            return {k: walk(v, names + [k]) for k, v in tree.items()}

        return walk(params, [])


def merge_adapter_params(params: dict, registry: AdapterRegistry,
                         name: str | None) -> dict:
    """``registry.merge`` that treats ``None`` (no adapter) as the base
    params — so callers can build every request's reference uniformly."""
    if not name:
        return params
    return registry.merge(params, name)


# ----------------------------------------------------------- file format


def save_adapter_file(path: str, factors: dict) -> None:
    """One adapter's (unscaled) factors as an ``.npz``: keys are
    ``"<target path>::A"`` / ``"::B"`` — what ``scripts/serve_worker.py
    --adapter name=path`` preloads."""
    flat = {}
    for tpath, fac in factors.items():
        flat[tpath + "::A"] = np.asarray(fac["A"], np.float32)
        flat[tpath + "::B"] = np.asarray(fac["B"], np.float32)
    np.savez(path, **flat)


def load_adapter_file(path: str) -> dict:
    """Inverse of :func:`save_adapter_file`."""
    out: dict[str, dict] = {}
    with np.load(path) as z:
        for key in z.files:
            tpath, _, part = key.rpartition("::")
            if part not in ("A", "B") or not tpath:
                raise ValueError(
                    f"{path}: key {key!r} is not '<target>::A|B'"
                )
            out.setdefault(tpath, {})[part] = z[key]
    for tpath, fac in out.items():
        if "A" not in fac or "B" not in fac:
            raise ValueError(f"{path}: target {tpath!r} missing A or B")
    return out


# --------------------------------------------------------- device cache


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_factor_row(pool: jax.Array, slot: jax.Array,
                      value: jax.Array) -> jax.Array:
    """Write one adapter's stacked factor (L, d_in, r) into row
    ``slot`` of the (L, slots+1, d_in, r) pool — a traced slot index,
    so one trace serves every (slot, adapter) upload of a given
    shape (the state_cache ``_set_row`` idiom on axis 1)."""
    v = value.astype(pool.dtype)[:, None]
    return jax.lax.dynamic_update_slice_in_dim(pool, v, slot, axis=1)


class AdapterCache:
    """Bounded device cache of adapter factor slots (see module
    docstring): the PagePool refcount/LRU discipline over stacked
    factor pools.  Row 0 of every pool is the reserved all-zero
    "no adapter" entry — never handed out, never written.

    ``acquire(name)`` returns the adapter's device slot (uploading the
    factors on a miss, evicting a zero-ref resident LRU-first) or
    ``None`` when every slot is pinned by refcounts — admission treats
    that exactly like a short KV page pool: wait, never OOM mid-
    flight.  ``release(name)`` drops one holder; a zero-ref adapter
    STAYS resident (warm for the next acquire) until evicted.
    ``version`` bumps on every pool write so the engine knows when to
    re-attach the pools to its param tree."""

    def __init__(self, registry: AdapterRegistry, slots: int,
                 compute_dtype=jnp.bfloat16):
        if slots < 1:
            raise ValueError(f"need >= 1 adapter cache slot, got {slots}")
        self.registry = registry
        self.slots = slots
        self.dtype = jnp.dtype(compute_dtype)
        r = registry.rank
        self.pools: dict[str, dict] = {
            path: {
                "A": jnp.zeros((n, slots + 1, d_in, r), self.dtype),
                "B": jnp.zeros((n, slots + 1, r, d_out), self.dtype),
            }
            for path, (n, d_in, d_out) in registry.targets.items()
        }
        self.version = 0  # bumps on every pool write (upload/evict)
        self._slot_of: dict[str, int] = {}  # resident adapter -> row
        self._refs: dict[str, int] = {}  # resident adapter -> holders
        self._lru: "OrderedDict[str, None]" = OrderedDict()  # zero-ref
        self._free: list[int] = list(range(1, slots + 1))
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    # -------------------------------------------------------------- state

    @property
    def resident_count(self) -> int:
        return len(self._slot_of)

    def resident(self, name: str) -> bool:
        """Is the adapter's factor set on-device right now?  A pure
        probe (no stats, no LRU touch) — the router's adapter-affinity
        placement term reads it."""
        return name in self._slot_of

    def resident_names(self) -> list[str]:
        return sorted(self._slot_of)

    def slot_of(self, name: str) -> int | None:
        return self._slot_of.get(name)

    def refcount(self, name: str) -> int:
        return self._refs.get(name, 0)

    # ---------------------------------------------------------- lifecycle

    def acquire(self, name: str) -> int | None:
        """Pin ``name``'s factors to a device slot and return its row
        id (>= 1), or ``None`` when every slot is pinned by other
        streams (the caller waits — admission's page-wait contract).
        Unknown names raise :class:`UnknownAdapterError` (via the
        registry) before any slot state changes."""
        factors = self.registry.factors(name)  # raises on unknown
        slot = self._slot_of.get(name)
        if slot is not None:
            self.hits += 1
            self._refs[name] = self._refs.get(name, 0) + 1
            self._lru.pop(name, None)
            return slot
        if self._free:
            slot = self._free.pop(0)
        else:
            victim = next(iter(self._lru), None)
            if victim is None:
                # every slot pinned: wait, never evict live.  NOT a
                # miss: admission retries this every engine step, and
                # counting each retry would drift the gauge (a miss is
                # one factor UPLOAD — the commit_lookup discipline)
                return None
            self._lru.pop(victim)
            slot = self._slot_of.pop(victim)
            self._refs.pop(victim, None)
            self.evictions += 1
            # no scrub pass: _upload overwrites EVERY target's rows
            # (explicit zeros for uncovered targets), so the evicted
            # tenant's factors cannot survive the reuse and a separate
            # erase would just double the device writes
        self.misses += 1  # one miss == one factor upload
        self._upload(slot, factors)
        self._slot_of[name] = slot
        self._refs[name] = 1
        return slot

    def release(self, name: str) -> None:
        """Drop one holder.  At zero the adapter stays RESIDENT but
        becomes LRU-evictable (warm reuse beats eager eviction; the
        pools are bounded either way).  Releasing below zero — or an
        adapter that was never acquired — raises the named
        :class:`AdapterCacheError`: always a caller bug."""
        rc = self._refs.get(name, 0)
        if name not in self._slot_of or rc <= 0:
            raise AdapterCacheError(
                f"release of adapter {name!r} with no holders "
                f"(double release, or never acquired)"
            )
        if rc == 1:
            self._refs[name] = 0
            self._lru[name] = None
            self._lru.move_to_end(name)
        else:
            self._refs[name] = rc - 1

    # ------------------------------------------------------------ uploads

    def _upload(self, slot: int, factors: dict) -> None:
        for path, pool in self.pools.items():
            fac = factors.get(path)
            for part in ("A", "B"):
                if fac is not None:
                    value = jnp.asarray(fac[part])
                else:
                    # target not covered by this adapter: its delta is
                    # zero — write the zero factors explicitly so a
                    # recycled slot can't leak the previous tenant's
                    value = jnp.zeros(
                        pool[part].shape[:1] + pool[part].shape[2:],
                        pool[part].dtype,
                    )
                pool[part] = _write_factor_row(
                    pool[part], jnp.int32(slot), value
                )
        self.version += 1


# ----------------------------------------------- param-tree integration


def attach_adapter_pools(params: dict, pools: dict) -> dict:
    """Splice the cache's factor pools into a (decode-cast) param tree:
    each target's projection dict gains ``"lora": {"A": pool, "B":
    pool}``.  Pure host-side dict surgery — no device work; the engine
    re-attaches after every cache upload (``AdapterCache.version``)."""

    def walk(tree, names):
        if not isinstance(tree, dict):
            return tree
        path = "/".join(names)
        if path in pools:
            return {**tree, "lora": dict(pools[path])}
        return {k: walk(v, names + [k]) for k, v in tree.items()}

    return walk(params, [])


def bind_adapter_ids(params, ids: jax.Array):
    """Bind the per-row adapter ids into every attached ``"lora"``
    subtree (called INSIDE the compiled tick/prefill/verify steps —
    pure tree surgery at trace time).  ``ids`` is the launch's (b,)
    int32 row->cache-slot map (the slot pool's ``meta["adapter_id"]``,
    gathered into lane order when the tick is narrow).  Stacked
    targets broadcast the ids over their leading layer axis so the
    scan-over-layers slices a per-layer copy alongside the factors.
    Trees without ``"lora"`` subtrees pass through untouched — the
    LoRA-off path is structurally identical to pre-LoRA."""

    def walk(tree):
        if not isinstance(tree, dict):
            return tree
        lora = tree.get("lora")
        if isinstance(lora, dict) and "A" in lora:
            n = lora["A"].shape[0]
            bound = jnp.broadcast_to(ids[None, :], (n,) + ids.shape)
            return {
                **{k: walk(v) for k, v in tree.items() if k != "lora"},
                "lora": {**lora, "ids": bound},
            }
        return {k: walk(v) for k, v in tree.items()}

    return walk(params)
