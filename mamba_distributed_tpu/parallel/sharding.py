"""Sharding rules: how params, optimizer state, and batches lay out on the mesh.

Data parallel (BASELINE config 2): batch axis over (data, fsdp); params
replicated — XLA inserts the gradient psum that DDP's bucketed NCCL
all-reduce did (/root/reference/train.py:86,219-221).

FSDP (config 3): additionally shard every large parameter (and its Adam
moments, which inherit the same spec) over the fsdp axis — ZeRO-3-style
param + optimizer-state sharding; XLA inserts the all-gathers/reduce-
scatters.  Layer-stacked block params (leading n_layer axis from the
scan-over-layers layout) shard a *non-layer* axis so `lax.scan` slices
locally instead of gathering the whole stack per step.

Tensor parallel (over the ``tensor`` axis): mixer weights shard their
d_inner-derived axis — in_proj/conv column-parallel, out_proj/dt_proj
row-parallel (mamba_ssm 2.2.2 carries the same, unused, ``process_group``
plumbing in its mixers, SURVEY.md §2.3).  This is GSPMD-correctness TP:
because in_proj/wqkv pack multiple segments (z|xBC|dt, q|k|v) on one
axis, an even column shard cuts inside segments and XLA inserts a
reshard after the projection rather than keeping every inner activation
sharded Megatron-style; losses are exactly single-device (tested), the
communication pattern is compiler-chosen.  A per-rank-permuted packed
layout would tighten it — future work, BASELINE configs don't use TP.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P



# (path-suffix pattern, axis-from-end carrying the d_inner/head dimension)
# column-parallel weights shard their output axis, row-parallel their input
_TP_RULES: tuple[tuple[tuple[str, ...], int], ...] = (
    (("mixer", "in_proj", "kernel"), -1),   # column
    (("mixer", "out_proj", "kernel"), -2),  # row
    (("mixer", "conv", "kernel"), -2),
    (("mixer", "conv", "bias"), -1),
    (("mixer", "x_proj", "kernel"), -2),    # row (input is sharded x)
    (("mixer", "dt_proj", "kernel"), -1),
    (("mixer", "dt_proj", "bias"), -1),
    (("mixer", "A_log"), -1),               # mamba2 (nh,); mamba1 handled below
    (("mixer", "dt_bias"), -1),
    (("mixer", "D"), -1),
    (("mixer", "norm", "weight"), -1),
    (("mixer", "wqkv", "kernel"), -1),
    (("mlp", "fc1", "kernel"), -1),
    (("mlp", "fc2", "kernel"), -2),
    (("moe", "w1"), -1),                    # (held, d, 2*di): column
    (("moe", "w2"), -2),                    # (held, di, d): row
    (("shared", "fc1", "kernel"), -1),      # the shared expert, an MLP
    (("shared", "fc2", "kernel"), -2),
)

# leaves whose first non-layer axis is the MoE expert dimension: the experts
# HELD (cfg.moe_held), which ``mesh.expert`` divides further in training; the
# router and the shared expert are every device's whole
_EXPERT_RULES: tuple[tuple[str, ...], ...] = (
    ("moe", "w1"),
    ("moe", "w2"),
)


def _tp_axis(names: list[str], ndim: int, stacked: bool) -> int | None:
    """Which axis (if any) of this param shards over the tensor axis."""
    for pattern, ax in _TP_RULES:
        k = len(pattern)
        if tuple(names[-k:]) == pattern:
            # mamba1's A_log is (di, n): the head/channel axis is -2 there
            if pattern[-1] == "A_log" and ndim - (1 if stacked else 0) == 2:
                ax = -2
            return ndim + ax
    return None


def _tp_rule_end_axis(names: list[str]) -> int | None:
    """The raw rule axis-from-end (-1 column-parallel, -2 row-parallel)
    for a param path, before any ndim conversion — what the serving
    LoRA factor rules key off (a factor's rank differs from its base
    kernel's, so the absolute-axis form is useless there)."""
    for pattern, ax in _TP_RULES:
        if tuple(names[-len(pattern):]) == pattern:
            return ax
    return None


def _spec_for(names: list[str], shape: tuple[int, ...], fsdp_size: int,
              tensor_size: int, stacked: bool, expert_size: int = 1) -> P:
    """Expert axis first (MoE stacks), then the tensor-parallel axis (by
    rule), then the largest remaining fsdp-divisible axis (skipping the
    layer axis of stacked params); replicate whatever doesn't divide."""
    spec: list = [None] * len(shape)
    if expert_size > 1:
        for pattern in _EXPERT_RULES:
            k = len(pattern)
            if tuple(names[-k:]) == pattern:
                ax = 1 if stacked else 0
                if shape[ax] % expert_size == 0:
                    spec[ax] = "expert"
                break
    if tensor_size > 1:
        ax = _tp_axis(names, len(shape), stacked)
        if ax is not None and shape[ax] % tensor_size == 0:
            spec[ax] = "tensor"
    if fsdp_size > 1:
        start = 1 if stacked and len(shape) > 1 else 0
        cands = [
            (shape[i], i)
            for i in range(start, len(shape))
            if spec[i] is None and shape[i] % fsdp_size == 0
        ]
        if cands:
            _, axis = max(cands)
            spec[axis] = "fsdp"
    if all(s is None for s in spec):
        return P()
    return P(*spec)


def param_specs(params, shard: bool, fsdp_size: int, tensor_size: int = 1,
                pipe_size: int = 1, expert_size: int = 1):
    """PartitionSpec pytree matching ``params``.

    ``shard=False`` disables FSDP; tensor parallelism applies whenever
    ``tensor_size > 1`` (it is a layout requirement, not an option).
    With ``pipe_size > 1`` the stacked blocks' leading layer axis shards
    over the pipe axis — each stage holds exactly its own layers, the
    layout ``parallel/pipeline.pipelined_layers`` consumes directly.
    """
    def leaf_spec(path, leaf):
        names = [str(getattr(k, "key", getattr(k, "idx", None))) for k in path]
        stacked = "blocks" in names or "attn_blocks" in names
        spec = _spec_for(
            names, np.shape(leaf),
            fsdp_size if shard else 1, tensor_size, stacked, expert_size,
        )
        if pipe_size > 1 and stacked and np.ndim(leaf) > 0:
            rest = tuple(spec)[1:]  # layer axis -> pipe; keep fsdp/tp tail
            spec = P("pipe", *rest)
        return spec

    return jax.tree_util.tree_map_with_path(leaf_spec, params)


def param_shardings(params, mesh: Mesh, shard: bool):
    specs = param_specs(
        params, shard, mesh.shape["fsdp"], mesh.shape["tensor"],
        dict(mesh.shape).get("pipe", 1),
        dict(mesh.shape).get("expert", 1),
    )
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def shard_params(params, mesh: Mesh, shard: bool):
    """device_put the param pytree with its shardings (lazy, async)."""
    shardings = param_shardings(params, mesh, shard)
    return jax.device_put(params, shardings)


def opt_state_shardings(opt_shapes, params, param_sharding_tree, mesh: Mesh):
    """Shardings for the optimizer state: Adam moments (and any other
    params-shaped leaf) inherit the matching parameter's sharding; scalars
    and everything else replicate on the mesh.

    Matching is by tree-path suffix: optax's ``mu``/``nu`` (and masked
    wrappers) mirror the param tree, so the param path is a suffix of the
    state leaf's path.
    """
    import jax.tree_util as jtu

    flat_params = jtu.tree_flatten_with_path(params)[0]
    by_path = {
        jtu.keystr(path): (np.shape(leaf), sh)
        for (path, leaf), sh in zip(
            flat_params, jax.tree.leaves(param_sharding_tree)
        )
    }
    replicated = NamedSharding(mesh, P())

    def leaf_shard(path, leaf):
        ks = jtu.keystr(path)
        for ppath, (shape, sh) in by_path.items():
            if ks.endswith(ppath) and np.shape(leaf) == shape:
                return sh
        return replicated

    return jtu.tree_map_with_path(leaf_shard, opt_shapes)


# ------------------------------------------- serving tensor parallelism


def serving_param_specs(params, model_shards: int, stage_shards: int = 1):
    """Per-parameter PartitionSpec pytree for SERVING weights over the
    serving mesh's ``model`` axis — and, at ``stage_shards > 1``, the
    leading LAYER axis of every layer-stacked leaf (``blocks``/
    ``attn_blocks`` subtrees, LoRA factor pools included) over the 3-D
    mesh's ``stage`` axis (parallel/mesh.serving_mesh).  Stage and
    model compose per leaf: axis 0 carries ``stage``, the TP rule axis
    carries ``model``; non-stacked leaves (embedding, head, final
    norm) stay stage-replicated.  A layer axis that doesn't divide by
    ``stage_shards`` replicates (``validate_serving_stage_shards``
    rejects that loudly at engine construction).

    The rules are the training ``_TP_RULES`` (every mixer weight's
    d_inner/head axis: Mamba in/out projections column/row-parallel,
    conv + SSM channel blocks over d_inner, attention wqkv/out_proj
    over heads, MLP/MoE inner axes) plus the two params training TP
    leaves replicated because the optimizer owns them there: the
    embedding and (untied) lm_head shard their VOCAB axis — the
    column-parallel head, the single biggest weight read of a decode
    tick.  Norm scales and anything whose rule axis doesn't divide
    evenly replicate.  ``model_shards == 1`` returns all-``P()``:
    byte-identical to the replicated pre-TP layout, so the knob's off
    position is the exact status quo.

    Slot/page state is NOT covered here — it partitions over ``data``
    only (``slot_pool_specs``); the two spec families compose because
    they name disjoint mesh axes.

    Int8-quantized serving trees (ops/quant.py) are covered too: a
    quantized leaf is ``{"kernel": int8, "scale": f32}`` whose scale
    keeps the kernel's rank with every non-channel axis sized 1 and
    whose CHANNEL axis is by construction the kernel's tensor-parallel
    axis — so a ``scale`` leaf simply rides its sibling kernel's rule
    (same path, same axis) and scales shard with their weights, no
    cross-shard rescale.  The quantized embedding's dict form
    (``embedding/kernel`` + ``embedding/scale``) keeps the vocab axis
    column-parallel exactly like the bare-array form.
    """
    def leaf_spec(path, leaf):
        names = [str(getattr(k, "key", getattr(k, "idx", None))) for k in path]
        shape = np.shape(leaf)
        spec: list = [None] * len(shape)
        if (stage_shards > 1 and shape
                and ("blocks" in names or "attn_blocks" in names)
                and shape[0] % stage_shards == 0):
            # layer-stacked leaf: stage owns whole layers (axis 0),
            # composing with whatever model-axis rule applies below
            spec[0] = "stage"
        if model_shards > 1 and len(names) >= 2 and names[-2] == "lora":
            # multi-tenant LoRA factor pools (serving/adapters.py):
            # "A" (L, slots+1, d_in, r) shards d_in with a ROW-parallel
            # base kernel's input axis (the x @ A contraction then runs
            # on the shard that holds that x slice; GSPMD all-reduces
            # the rank-r partials with the base matmul's), "B"
            # (L, slots+1, r, d_out) shards d_out with a COLUMN-
            # parallel kernel's output axis (the delta lands sharded
            # exactly like y).  The other factor of each pair — and
            # the bound "ids" rows — replicate (rank-r tensors are
            # tiny).  This is what makes LoRA and tensor parallelism
            # compose with zero cross-shard rescales.
            base_ax = _tp_rule_end_axis(names[:-2] + ["kernel"])
            ax = None
            if names[-1] == "A" and base_ax == -2:
                ax = len(shape) - 2  # d_in
            elif names[-1] == "B" and base_ax == -1:
                ax = len(shape) - 1  # d_out
            if ax is not None and shape[ax] % model_shards == 0:
                spec[ax] = "model"
            if all(s is None for s in spec):
                return P()
            return P(*spec)
        if model_shards > 1 and shape:
            lookup = names
            if names and names[-1] == "scale":
                # an int8 scale shards its kernel's axis (rank matches:
                # the scale keeps the kernel's rank, channel axis full)
                lookup = names[:-1] + ["kernel"]
            stacked = "blocks" in lookup or "attn_blocks" in lookup
            ax = _tp_axis(lookup, len(shape), stacked)
            if ax is None:
                if (lookup[-1] == "embedding"
                        or lookup[-2:] == ["embedding", "kernel"]):
                    ax = 0  # (V, d): vocab axis
                elif lookup[-2:] == ["lm_head", "kernel"]:
                    ax = len(shape) - 1  # (d, V): vocab axis
            if ax is not None and shape[ax] % model_shards == 0:
                spec[ax] = "model"
        if all(s is None for s in spec):
            return P()  # the literal pre-TP replicated spec
        return P(*spec)

    return jax.tree_util.tree_map_with_path(leaf_spec, params)


def serving_param_shardings(params, mesh: Mesh):
    """NamedSharding pytree for serving weights on a ``serving_mesh``
    (device_put at engine init / ``generate(mesh=)``; the compiled tick
    and chunk step re-assert it via sharding constraints so the layout
    can never decay mid-flight)."""
    specs = serving_param_specs(
        params, dict(mesh.shape).get("model", 1),
        dict(mesh.shape).get("stage", 1),
    )
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def constrain_serving_params(params, mesh):
    """``with_sharding_constraint`` the (decode-cast) params to their
    serving tensor-parallel layout — THE one constraint every compiled
    consumer applies (engine tick / one-shot prefill / chunk step /
    ``generate(mesh=)``), kept in a single place so the four call sites
    can never drift apart and break the engine==generate() bit-parity
    contract.  ``mesh=None`` is a no-op (the unsharded paths)."""
    if mesh is None:
        return params
    return jax.lax.with_sharding_constraint(
        params, serving_param_shardings(params, mesh)
    )


def validate_serving_model_shards(cfg, model_shards: int) -> None:
    """Reject a ``serving_model_shards`` the model's dimensions cannot
    tile — at ENGINE CONSTRUCTION, with the offending dimension named,
    instead of an opaque GSPMD error (or a silently replicated weight)
    mid-flight.  The checks mirror the axes ``serving_param_specs``
    actually shards — including the mamba2 PACKED projection widths
    (z|xBC|dt on one axis), which can be indivisible even when
    ``d_inner`` divides.  ``cfg`` is a ModelConfig."""
    if model_shards <= 1:
        return
    problems = []
    if cfg.d_inner % model_shards:
        problems.append(
            f"d_inner={cfg.d_inner} (expand * d_model — the Mamba "
            f"in/out projection and conv/SSM channel axis)"
        )
    if cfg.ssm_layer == "mamba2":
        g, ds, nh = cfg.ngroups, cfg.effective_d_state, cfg.nheads
        d_in_proj = 2 * cfg.d_inner + 2 * g * ds + nh
        conv_dim = cfg.d_inner + 2 * g * ds
        if nh % model_shards:
            problems.append(
                f"nheads={nh} (d_inner/headdim — the per-head "
                f"A_log/dt_bias/D axis and the dt segment of in_proj)"
            )
        if d_in_proj % model_shards:
            problems.append(
                f"in_proj width {d_in_proj} (the packed "
                f"2*d_inner + 2*ngroups*d_state + nheads column axis)"
            )
        if conv_dim % model_shards:
            problems.append(
                f"conv width {conv_dim} (d_inner + 2*ngroups*d_state)"
            )
    if cfg.vocab_size_padded % model_shards:
        problems.append(
            f"padded vocab={cfg.vocab_size_padded} (the embedding/"
            f"lm_head vocab axis)"
        )
    if cfg.attn_layer_idx:
        nh = cfg.effective_attn_num_heads
        nkv = cfg.effective_attn_num_kv_heads
        if nh % model_shards:
            problems.append(f"attn_num_heads={nh}")
        if nkv % model_shards:
            problems.append(f"attn_num_kv_heads={nkv}")
    if problems:
        raise ValueError(
            f"serving_model_shards={model_shards} does not divide "
            + "; ".join(problems)
            + " — pick a divisor of every listed dimension (or 1 to "
              "replicate weights)"
        )


def validate_serving_stage_shards(cfg, stage_shards: int) -> None:
    """Reject a ``serving_stage_shards`` the model's LAYER STACKS
    cannot tile — at ENGINE CONSTRUCTION, with the offending stack
    named, instead of an opaque GSPMD error (or a silently replicated
    stack) mid-flight.  The stage axis shards the leading layer axis of
    every stacked family, so EACH family must divide: pure-SSM stacks
    need ``n_layer % stage_shards == 0``; hybrid stacks need both the
    mamba stack (``n_layer - n_attn``) and the attention stack
    (``n_attn``) to divide — a stage owns whole layers of each family.
    No rung of the tick's ladder is required: the microbatched schedule
    (parallel/pipeline.pipelined_decode_layers) buckets whatever lane
    width the launch runs at, narrow or full-capacity, and launches
    the schedule cannot microbatch fall back to the stage-sharded
    GSPMD scan.  ``cfg`` is a ModelConfig."""
    if stage_shards <= 1:
        return
    problems = []
    n_attn = len(cfg.attn_layer_idx)
    n_mamba = cfg.n_layer - n_attn
    if cfg.n_layer % stage_shards:
        problems.append(f"n_layer={cfg.n_layer} (the layer stack)")
    if n_attn:
        if n_mamba % stage_shards:
            problems.append(
                f"mamba stack={n_mamba} (n_layer - the "
                f"{n_attn} attention layers — the hybrid 'blocks' "
                f"family shards separately)"
            )
        if n_attn % stage_shards:
            problems.append(
                f"attention stack={n_attn} (the hybrid 'attn_blocks' "
                f"family — per-layer KV page pools shard with it)"
            )
    if problems:
        raise ValueError(
            f"serving_stage_shards={stage_shards} does not divide "
            + "; ".join(problems)
            + " — pick a divisor of every listed stack (or 1 to keep "
              "the layer stacks unsharded)"
        )


# --------------------------------------------------- serving slot pool


def slot_pool_specs(pool, num_shards: int, stage_shards: int = 1):
    """PartitionSpec pytree for a serving slot pool (serving/state_cache
    .init_pool) sharded over a ``serving_mesh``'s data axis — and, at
    ``stage_shards > 1``, its per-LAYER leaves over the 3-D mesh's
    stage axis.

    The SLOT axis partitions: ``blocks`` leaves are (L, S, ...) and
    ``attn_blocks`` page-pool leaves (A, P+1, nkv, page, hd) shard the
    POOL axis 1 — the page-count axis, not the per-page token axis 3
    (head-major storage keeps the pool axis in the same position, so
    the data-axis tiling is layout-independent);
    ``logits`` (S, V) and every ``meta`` leaf (S, ...) shard axis 0.
    An axis that doesn't divide by ``num_shards`` replicates (the
    engine sizes capacity and the page pool so both divide; the
    fallback keeps arbitrary pools valid).  Weights are NOT covered
    here — serving replicates them (``NamedSharding(mesh, P())``).

    A NARROW tick's lane trees ride the same rules (the bucketed
    slot-pool constraint): ``state_cache.gather_rows``/
    ``scatter_rows`` pass their ``{"blocks", "logits", "meta"}``
    trees through here with the rung's lanes in place of the slot
    axis — the engine keeps a rung a multiple of the data-shard
    count and maps each shard's live slots onto that shard's lanes,
    so a compact lane tree tiles over ``data`` exactly like the full
    pool it was gathered from (docs/SERVING.md "Occupancy-adaptive
    ticks").

    STAGE tiling (``stage_shards > 1``, the 3-D mesh): the per-layer
    leaves — ``blocks`` conv/SSM carry stacks (L, S, ...) and the
    ``attn_blocks`` per-layer page pools (A, P+1, ...) — additionally
    shard their leading LAYER axis over ``stage``, so each stage owns
    exactly its own layers' decode state alongside its weight shard
    (pipeline residency; a layer axis that doesn't divide replicates,
    rejected loudly by ``validate_serving_stage_shards``).  The
    data-axis rules above are stage-blind and unchanged — ``logits``/
    ``meta`` have no layer axis and never name ``stage`` — and the
    host ``PagePool`` bookkeeping stays data-only: the stage axis
    tiles the LAYER axis of the page pools, never the page ranges.
    """
    def leaf_spec(path, leaf):
        names = [str(getattr(k, "key", getattr(k, "idx", None))) for k in path]
        shape = np.shape(leaf)
        stacked = "blocks" in names or "attn_blocks" in names
        ax = 1 if stacked else 0
        spec: list = [None] * len(shape)
        if len(shape) > ax and shape[ax] % num_shards == 0:
            spec[ax] = "data"
        if (stage_shards > 1 and stacked and shape
                and shape[0] % stage_shards == 0):
            spec[0] = "stage"
        return P(*spec)

    return jax.tree_util.tree_map_with_path(leaf_spec, pool)


def slot_pool_shardings(pool, mesh: Mesh):
    """NamedSharding pytree for the slot pool over ``mesh``'s data axis
    (and its layer stacks over a 3-D mesh's stage axis — device_put at
    engine init; re-asserted by the tick's sharding constraints every
    step so insert/evict propagation can never decay the layout)."""
    specs = slot_pool_specs(pool, mesh.shape["data"],
                            dict(mesh.shape).get("stage", 1))
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def slot_axis_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for host-owned per-slot arrays the tick takes as plain
    arguments (the hybrid page table (S, B) and lengths (S,)): leading
    slot axis over data."""
    return NamedSharding(mesh, P("data"))


def batch_spec(mesh: Mesh, seq_sharded: bool = False) -> P:
    """(B, T) batches: B over (data, fsdp, expert) — expert doubles as a
    pure-DP batch axis for the non-MoE layers — T over seq when SP is on."""
    if dict(mesh.shape).get("expert", 1) > 1:
        return P(("data", "fsdp", "expert"), "seq" if seq_sharded else None)
    return P(("data", "fsdp"), "seq" if seq_sharded else None)


def batch_sharding(mesh: Mesh, seq_sharded: bool = False) -> NamedSharding:
    return NamedSharding(mesh, batch_spec(mesh, seq_sharded))
