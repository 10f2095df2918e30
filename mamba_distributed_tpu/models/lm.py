"""Full language model: embedding -> N blocks -> final norm -> tied head.

Semantics match the reference wrapper + its dep
(``/root/reference/model.py:25-47`` — loss is plain cross-entropy against
the loader's pre-shifted targets — and ``mamba_ssm.models.mixer_seq_simple.
MixerModel``/``create_block``: prenorm blocks, fp32 residual stream, tied
embeddings, fused add+RMSNorm between blocks, optional gated MLP when
``d_intermediate > 0``, optional attention layers at ``attn_layer_idx``).
With ``cfg.attn_parallel`` (Falcon-H1) every block is the PARALLEL form: a
Mamba-2 mixer and attention read the one normed input and their scaled
outputs add into the residual; the parameters are then one stack,
``blocks``, whose entries hold ``mixer`` and ``attn`` side by side.

TPU-native structure: homogeneous stacks run as ``lax.scan`` over
layer-stacked parameters (one compiled block body regardless of depth,
which is also the FSDP-friendly layout — shard the non-layer axes and the
scan slices locally).  Hybrid stacks with a *periodic* attention pattern
(one attn layer every ``period`` layers — BASELINE config 5's shape) run
as a scan over supersteps of ``[offset mamba] -> attn -> [rest mamba]``,
so trace/compile cost is O(period), not O(n_layer); aperiodic patterns
fall back to a per-layer Python unroll (compile-time bound pinned by
tests/test_model.py).  Per-block ``jax.checkpoint`` implements activation
rematerialization.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from mamba_distributed_tpu.config import ModelConfig
from mamba_distributed_tpu.models.attention import (
    attention_mixer,
    attention_mixer_chunk,
    attention_mixer_step,
    attention_page_meta,
    init_attention_params,
    init_attention_state,
    pack_attention_pages,
)
from mamba_distributed_tpu.models.common import init_linear, linear
from mamba_distributed_tpu.obs import scopes
from mamba_distributed_tpu.models.mamba1 import (
    init_mamba1_params,
    init_mamba1_state,
    mamba1_mixer,
    mamba1_mixer_step,
)
from mamba_distributed_tpu.models.mamba2 import (
    init_mamba2_params,
    init_mamba2_state,
    mamba2_mixer,
    mamba2_mixer_step,
)
from mamba_distributed_tpu.ops.norm import add_rms_norm, rms_norm


def _init_mixer(key: jax.Array, cfg: ModelConfig) -> dict:
    if cfg.ssm_layer == "mamba2":
        return init_mamba2_params(key, cfg)
    if cfg.ssm_layer == "mamba1":
        return init_mamba1_params(key, cfg)
    raise ValueError(cfg.ssm_layer)


def _mixer_fwd(params, cfg, u, seq_ctx=None):
    fn = mamba2_mixer if cfg.ssm_layer == "mamba2" else mamba1_mixer
    return fn(params, cfg, u, seq_ctx=seq_ctx)


def _init_block(key: jax.Array, cfg: ModelConfig, attn: bool) -> dict:
    k_mix, k_mlp = jax.random.split(key)
    p = {
        "norm": {"weight": jnp.ones((cfg.d_model,), jnp.float32)},
        "mixer": init_attention_params(k_mix, cfg) if attn else _init_mixer(k_mix, cfg),
    }
    if cfg.attn_parallel:
        p["attn"] = init_attention_params(jax.random.fold_in(k_mix, 1), cfg)
    if cfg.d_intermediate > 0:
        import math

        rescale = (
            1.0 / math.sqrt(2 * cfg.n_layer)
            if cfg.rescale_prenorm_residual else 1.0
        )
        p["norm2"] = {"weight": jnp.ones((cfg.d_model,), jnp.float32)}
        if cfg.moe_num_experts:
            E = cfg.moe_num_experts
            first, held = cfg.moe_held
            k_r, k_e = jax.random.split(k_mlp)

            def one_expert(k):
                k1, k2 = jax.random.split(k)
                return (
                    init_linear(k1, cfg.d_model, 2 * cfg.d_intermediate,
                                False)["kernel"],
                    init_linear(k2, cfg.d_intermediate, cfg.d_model,
                                False)["kernel"] * rescale,
                )

            # an expert's draw is its own key's whatever the share held
            w1, w2 = jax.vmap(one_expert)(
                jax.random.split(k_e, E)[first:first + held])
            p["moe"] = {
                "router": init_linear(k_r, cfg.d_model, E, False),
                "w1": w1,  # (held, d, 2*di)
                "w2": w2,  # (held, di, d)
            }
            if cfg.moe_shared_intermediate:
                ds = cfg.moe_shared_intermediate
                k1, k2 = jax.random.split(jax.random.fold_in(k_mlp, 1))
                p["shared"] = {
                    "fc1": init_linear(k1, cfg.d_model, 2 * ds, False),
                    "fc2": init_linear(k2, ds, cfg.d_model, False),
                }
                p["shared"]["fc2"]["kernel"] = (
                    p["shared"]["fc2"]["kernel"] * rescale)
        else:
            k1, k2 = jax.random.split(k_mlp)
            p["mlp"] = {
                "fc1": init_linear(k1, cfg.d_model, 2 * cfg.d_intermediate, False),
                "fc2": init_linear(k2, cfg.d_intermediate, cfg.d_model, False),
            }
            # fc2 is the second residual projection; depth-rescale like out_proj
            p["mlp"]["fc2"]["kernel"] = p["mlp"]["fc2"]["kernel"] * rescale
    return p


def _embed(params: dict, cfg: ModelConfig, ids: jax.Array) -> jax.Array:
    """Embedding lookup, transparent to int8 serving quantization
    (ops/quant.py): a quantized embedding is ``{"kernel": int8 (V, d),
    "scale": f32 (V, 1)}`` with one scale per vocab row, so the lookup
    dequantizes just the gathered rows.  ``cfg.embedding_multiplier``
    scales the rows in float32, before the one rounding."""
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    emb = params["embedding"]
    with jax.named_scope(scopes.EMBED):
        if isinstance(emb, dict):
            # dequantize in f32 (scales keep full precision — same rule as
            # linear() and _tied_logits), then cast once
            rows = emb["kernel"][ids].astype(jnp.float32) * emb["scale"][ids]
        else:
            rows = emb[ids]
        if cfg.embedding_multiplier != 1.0:
            rows = rows.astype(jnp.float32) * cfg.embedding_multiplier
        return rows.astype(compute_dtype)


def _tied_logits(params: dict, normed: jax.Array, compute_dtype) -> jax.Array:
    """Tied LM head: ``normed @ embedding.T`` with fp32 accumulation.
    A quantized embedding's per-vocab-row scales become per-OUTPUT
    scales of the head matmul — ``(x @ q.T) * scale`` on the fp32
    accumulator, no dequantized weight copy (ops/quant.py)."""
    emb = params["embedding"]
    if isinstance(emb, dict):
        y = jnp.dot(
            normed.astype(compute_dtype),
            emb["kernel"].T.astype(compute_dtype),
            preferred_element_type=jnp.float32,
        )
        return y * emb["scale"][:, 0].astype(jnp.float32)
    return jnp.dot(
        normed.astype(compute_dtype),
        emb.T.astype(compute_dtype),
        preferred_element_type=jnp.float32,
    )


def _gated_mlp(params: dict, x: jax.Array, compute_dtype,
               multipliers: tuple = ()) -> jax.Array:
    """GatedMLP (mamba_ssm modules/mlp.py): fc2(y * silu(gate)).
    ``multipliers`` (``cfg.mlp_multipliers``: on the gate before the SiLU,
    on the down-projection's output) are the published scalars of a config
    that has them."""
    with jax.named_scope(scopes.MLP):
        yz = linear(params["fc1"], x, compute_dtype)
        y, gate = jnp.split(yz, 2, axis=-1)
        gate = gate.astype(jnp.float32)
        if multipliers:
            gate = gate * multipliers[0]
        out = linear(params["fc2"], y * jax.nn.silu(gate).astype(y.dtype),
                     compute_dtype)
        if multipliers:
            out = (out.astype(jnp.float32) * multipliers[1]).astype(out.dtype)
        return out


# The dropless expert layer keeps ONE form per entry, chosen from the shape
# it is traced at.  Up to this many rows (a decode tick's lanes, a verify
# chunk) every held expert runs over every row with the gate as a mask:
# a row costs an expert 6 * d * di operations against the 6 * d * di bytes
# of bfloat16 weights the expert's read takes anyway, so under the chip's
# ridge (240 operations a byte on a v5e) the read bounds the time and the
# form adds none, needs no sort and no gather, and takes the same time
# whatever the routing.  Over it (a prefill chunk, a training batch) the
# rows are sorted by expert into a buffer of the most that can land here
# and multiplied in groups (``jax.lax.ragged_dot``), the tail masked.
MOE_DENSE_MAX_ROWS = 128


def _moe_mlp(params: dict, cfg: ModelConfig, x: jax.Array, compute_dtype,
             row_mask=None):
    """Token-choice top-k mixture of gated-MLP experts, DROPLESS, over the
    experts this program holds -> (out, aux, load).

    The router scores all ``moe_num_experts`` in float32; a row's
    ``moe_top_k`` experts are the top-k of the LOGITS and its gates a
    float32 softmax over those k alone (the published order; a near-tie
    between the k-th and the next logit is the one place where rounding
    changes which expert runs).  Of the row's choices, those whose expert
    lies in ``cfg.moe_held`` are computed here, every one of them whatever
    the expert's load: no capacity and no drop.  The others are left out:
    their experts live elsewhere and nothing stands in for them or for an
    exchange.  ``out`` is the sum over the row's held choices of
    ``gate * w2[e](y * silu(gate_proj))``.

    Shapes are static in both forms (``MOE_DENSE_MAX_ROWS`` above), so a
    seed's routing changes no program, and what a row is answered does not
    depend on the rows beside it.

    ``aux`` is the Switch load-balance loss E * sum_e f_e * P_e (== 1 at
    perfect balance) over all ``moe_num_experts``, averaged into lm_loss
    with weight cfg.moe_aux_weight.  ``load`` ((held + 1,) int32) counts,
    a held expert, the rows routed to it, and last the held experts that
    any row reached; of the rows ``row_mask`` (x.shape[:-1]; None: all)
    marks, since a pad lane's or a pad position's rows are computed like
    any other and answer nobody.
    """
    E, k = cfg.moe_num_experts, cfg.moe_top_k
    first, held = cfg.moe_held
    lead, d = x.shape[:-1], x.shape[-1]
    xt = x.reshape(-1, d)
    n = xt.shape[0]
    cd = compute_dtype

    with jax.named_scope(scopes.ROUTER):
        logits = linear(params["router"], xt, jnp.float32)       # (n, E)
        top_v, top_e = jax.lax.top_k(logits, k)                  # (n, k)
        gates = jax.nn.softmax(top_v, axis=-1)                   # over the k
        local = top_e - first
        here = (local >= 0) & (local < held)
        # (n, k, held): the row's choice j is held expert e
        oh = (local[..., None] == jnp.arange(held)) & here[..., None]
        rows_e = jnp.sum(oh, axis=1, dtype=jnp.int32)            # (n, held)
        sizes = jnp.sum(rows_e, axis=0)                          # (held,)
        load = sizes if row_mask is None else jnp.sum(
            jnp.where(row_mask.reshape(n, 1) > 0, rows_e, 0), axis=0)
        load = jnp.append(load, jnp.sum(load > 0, dtype=jnp.int32))

    if n <= MOE_DENSE_MAX_ROWS:
        with jax.named_scope(scopes.EXPERTS):
            gate_e = jnp.sum(jnp.where(oh, gates[..., None], 0.0), axis=1)
            yz = jnp.einsum("nd,edf->enf", xt.astype(cd),
                            params["w1"].astype(cd),
                            preferred_element_type=jnp.float32)
            y, g = jnp.split(yz, 2, axis=-1)
            # the gate enters before the down-projection, which is linear:
            # one product over (expert, width) then sums the routed terms
            h = (y * jax.nn.silu(g) * gate_e.T[..., None]).astype(cd)
            out = jnp.einsum("enf,efd->nd", h, params["w2"].astype(cd),
                             preferred_element_type=jnp.float32)
    else:
        with jax.named_scope(scopes.ROUTER):
            # every (row, choice) pair keyed by its held expert, the pairs
            # of absent experts last; a stable sort groups them
            key = jnp.where(here, local, held).reshape(-1)       # (n*k,)
            order = jnp.argsort(key, stable=True)
            rows = xt.astype(cd)[order // k]                     # (n*k, d)
            g_sorted = jnp.where(here, gates, 0.0).reshape(-1)[order]
        with jax.named_scope(scopes.EXPERTS):
            yz = jax.lax.ragged_dot(rows, params["w1"].astype(cd), sizes,
                                    preferred_element_type=jnp.float32)
            y, g = jnp.split(yz, 2, axis=-1)
            h = (y * jax.nn.silu(g) * g_sorted[:, None]).astype(cd)
            ye = jax.lax.ragged_dot(h, params["w2"].astype(cd), sizes,
                                    preferred_element_type=jnp.float32)
            # the tail past the groups holds no product of a held expert
            ye = jnp.where((jnp.arange(n * k) < jnp.sum(sizes))[:, None],
                           ye, 0.0)
            # back to (row, choice) order and summed over a row's choices
            # in that order, whoever shares the buffer
            out = jnp.sum(ye[jnp.argsort(order)].reshape(n, k, d), axis=1)

    # Switch aux: fraction routed to e (over all k choices) x mean prob
    probs = jax.nn.softmax(logits, axis=-1)
    f = jnp.mean(jnp.sum(jax.nn.one_hot(top_e, E, dtype=jnp.float32),
                         axis=1), axis=0)                        # (E,)
    aux = E * jnp.sum(f * jnp.mean(probs, axis=0)) / k
    return out.reshape(*lead, d).astype(x.dtype), aux, load


def _expert_layer(bp: dict, cfg: ModelConfig, x: jax.Array, compute_dtype,
                  row_mask=None):
    """The routed experts held here plus the shared expert every row
    passes -> (out, aux, load), all under the scope ``moe``."""
    with jax.named_scope(scopes.MOE):
        out, aux, load = _moe_mlp(bp["moe"], cfg, x, compute_dtype, row_mask)
        if cfg.moe_shared_intermediate:
            out = out + _gated_mlp(bp["shared"], x, compute_dtype)
    return out, aux, load


def _scale_residual(cfg: ModelConfig, out):
    """A half-block's output under ``cfg.residual_multiplier``, in float32
    before the one rounding; 1.0 applies nothing and traces nothing."""
    if cfg.residual_multiplier == 1.0:
        return out
    return (out.astype(jnp.float32) * cfg.residual_multiplier).astype(out.dtype)


def _mamba_mix_fwd(mp, cfg, normed, seq_ctx, return_state, token_mask,
                   initial_state):
    """A block's Mamba mixer over a sequence -> (out, decode state or None);
    ``initial_state`` is a previous chunk's ``(conv_state, ssm_state)``."""
    if not return_state:
        return _mixer_fwd(mp, cfg, normed, seq_ctx=seq_ctx), None
    mix = mamba2_mixer if cfg.ssm_layer == "mamba2" else mamba1_mixer
    ics, iss = (None, None) if initial_state is None else initial_state
    return mix(
        mp, cfg, normed, return_final_state=True, token_mask=token_mask,
        initial_conv_state=ics, initial_ssm_state=iss,
    )


def _attn_mix_fwd(ap, cfg, normed, seq_ctx, return_state, token_mask,
                  initial_state):
    """A block's attention over a sequence -> (out, state or None);
    ``initial_state`` is ``((k_pages, v_pages), layer, page_table,
    lengths)`` and the state then the whole pool again, written."""
    if initial_state is not None:
        # chunked prefill: resume against the paged KV cache; the mask'd
        # pad prefix is handled inside (pad keys are never written to
        # pages, so nothing can attend them)
        kv, layer, page_table, lengths = initial_state
        return attention_mixer_chunk(
            ap, cfg, normed, kv, layer, page_table, lengths,
            token_mask=token_mask,
        )
    if token_mask is not None:
        raise ValueError(
            "token_mask one-shot prefill is SSM-only: full-sequence "
            "attention would attend the pad keys; hybrid bucketed "
            "prompts go through the chunk step instead "
            "(serving/prefill.py)"
        )
    if return_state:
        return attention_mixer(ap, cfg, normed, return_final_state=True)
    return attention_mixer(ap, cfg, normed, seq_ctx=seq_ctx), None


def _add_branches(cfg: ModelConfig, ssm_out, attn_out):
    """A parallel block's sum: each half under its published scalar."""
    return (ssm_out.astype(jnp.float32) * cfg.ssm_out_multiplier
            + attn_out.astype(jnp.float32) * cfg.attention_out_multiplier
            ).astype(ssm_out.dtype)


def _attn_input(cfg: ModelConfig, normed):
    """A parallel block's attention input, under its published scalar."""
    if cfg.attention_in_multiplier == 1.0:
        return normed
    return normed * jnp.asarray(cfg.attention_in_multiplier, normed.dtype)


def _block_fwd(block_params, cfg, hidden, residual, attn: bool = False,
               seq_ctx=None, return_state: bool = False, token_mask=None,
               initial_state=None):
    """One prenorm block: fused add+norm -> mixer [-> add+norm -> MLP/MoE].

    ``attn``: in a two-stack model, whether this block is an attention
    block.  A PARALLEL block (``cfg.attn_parallel``) runs a Mamba mixer
    and attention on the one normed input and adds them; it is not asked.

    ``return_state=True`` (prefill) returns ``(hidden, residual, state,
    load)``: the block's decode state, always the pair (Mamba's,
    attention's) with None for the half a block lacks (the conv+SSM caches,
    the attention K/V), and the expert layer's ``load`` (``_moe_mlp``, of
    the positions ``token_mask`` marks; None for a dense block).  ``token_mask``
    (prefill only) zeroes the mixer's scan inputs at left-pad positions
    (inference/bucketing.py).  ``initial_state`` (chunked prefill) is the
    same pair from the previous chunk: the ``(conv_state, ssm_state)``
    carry for SSM mixers, ``((k_pages, v_pages), layer, page_table,
    lengths)`` for attention mixers — the whole paged KV pool and this
    layer's index into it; the chunk writes the layer's pages in place and
    the state returned is the whole pool again (lm_prefill_chunk).
    With a MoE model (``cfg.moe_num_experts > 0``) the non-state form
    returns ``(hidden, residual, aux)`` — the layer's load-balance loss
    term.
    """
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    residual_dtype = jnp.float32 if cfg.residual_in_fp32 else compute_dtype
    if hidden is None:
        # single-carry form (lm_forward scans): ``residual`` is already the
        # post-add stream; only the norm remains
        residual = residual.astype(residual_dtype)
        normed = rms_norm(
            residual, block_params["norm"]["weight"], cfg.norm_eps
        ).astype(compute_dtype)
    else:
        normed, residual = add_rms_norm(
            hidden, residual, block_params["norm"]["weight"], cfg.norm_eps,
            residual_dtype=residual_dtype,
        )
    init_m, init_a = (None, None) if initial_state is None else initial_state
    st_m = st_a = None
    if cfg.attn_parallel:
        with jax.named_scope(scopes.SSM_BRANCH):
            ssm_out, st_m = _mamba_mix_fwd(
                block_params["mixer"], cfg, normed, seq_ctx, return_state,
                token_mask, init_m)
        with jax.named_scope(scopes.ATTN_BRANCH):
            attn_out, st_a = _attn_mix_fwd(
                block_params["attn"], cfg, _attn_input(cfg, normed), seq_ctx,
                return_state, token_mask, init_a)
        hidden = _add_branches(cfg, ssm_out, attn_out)
    elif attn:
        hidden, st_a = _attn_mix_fwd(
            block_params["mixer"], cfg, normed, seq_ctx, return_state,
            token_mask, init_a)
    else:
        hidden, st_m = _mamba_mix_fwd(
            block_params["mixer"], cfg, normed, seq_ctx, return_state,
            token_mask, init_m)
    hidden = _scale_residual(cfg, hidden)
    aux, load = jnp.zeros((), jnp.float32), None
    if cfg.d_intermediate > 0:
        normed, residual = add_rms_norm(
            hidden, residual, block_params["norm2"]["weight"], cfg.norm_eps,
            residual_dtype=jnp.float32 if cfg.residual_in_fp32 else compute_dtype,
        )
        if cfg.moe_num_experts:
            hidden, aux, load = _expert_layer(
                block_params, cfg, normed, compute_dtype, token_mask
            )
        else:
            hidden = _gated_mlp(block_params["mlp"], normed, compute_dtype,
                                cfg.mlp_multipliers)
        hidden = _scale_residual(cfg, hidden)
    if return_state:
        return hidden, residual, (st_m, st_a), load
    if cfg.moe_num_experts:
        return hidden, residual, aux
    return hidden, residual


def _final_norm(params, cfg: ModelConfig, hidden, residual):
    """Final (fused add+)norm of the stream.  ``hidden=None`` means
    ``residual`` is already the post-add stream (single-carry form) and
    only the norm is applied.  Shared by _final_logits and the blocked-CE
    loss path so their numerics cannot diverge."""
    residual_dtype = (
        jnp.float32 if cfg.residual_in_fp32 else jnp.dtype(cfg.compute_dtype)
    )
    if hidden is None:
        return rms_norm(
            residual.astype(residual_dtype), params["norm_f"]["weight"],
            cfg.norm_eps,
        )
    normed, _ = add_rms_norm(
        hidden, residual, params["norm_f"]["weight"], cfg.norm_eps,
        residual_dtype=residual_dtype,
    )
    return normed


def _head_matrix(params, cfg: ModelConfig):
    """(V, d) LM-head matrix: the tied embedding, or the lm_head kernel
    transposed (bias-free by construction — init_lm_params builds it with
    ``init_linear(..., bias=False)``)."""
    if cfg.tie_embeddings:
        return params["embedding"]
    if "bias" in params["lm_head"]:  # not an assert: must survive python -O
        raise ValueError(
            "blocked CE assumes a bias-free lm_head; a bias would be "
            "silently ignored, training against a wrong loss"
        )
    return params["lm_head"]["kernel"].T


def _head_logits(params, cfg: ModelConfig, normed):
    """The LM head over a normed stream -> float32 logits, under
    ``cfg.lm_head_multiplier`` where a config states one."""
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    if cfg.tie_embeddings:
        logits = _tied_logits(params, normed, compute_dtype)
    else:
        logits = linear(
            params["lm_head"], normed, compute_dtype
        ).astype(jnp.float32)
    if cfg.lm_head_multiplier != 1.0:
        logits = logits * cfg.lm_head_multiplier
    return logits


def _final_logits(params, cfg: ModelConfig, hidden, residual):
    """Final fused add+norm -> (tied) LM head, fp32-accumulated."""
    with jax.named_scope(scopes.LM_HEAD_LOSS):
        normed = _final_norm(params, cfg, hidden, residual)
        return _head_logits(params, cfg, normed)


def _remat(fn, cfg: ModelConfig, static_argnums=()):
    """Per-block checkpointing with the configured save policy."""
    if cfg.remat_policy == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    elif cfg.remat_policy == "mixer":
        # save the scan/attention outputs (~12-25 MB/layer bf16) so the
        # backward recomputes only the projections/conv/norms, never the
        # SSD chunked scan itself
        policy = jax.checkpoint_policies.save_only_these_names("mixer_out")
    else:
        policy = None
    return jax.checkpoint(fn, policy=policy, static_argnums=static_argnums)


def _two_stacks(cfg: ModelConfig) -> bool:
    """True where attention layers stand IN PLACE of Mamba layers: the
    parameters are then two stacks, ``blocks`` and ``attn_blocks``.  A
    parallel-block model has attention in every layer and one stack."""
    return bool(cfg.attn_layer_idx) and not cfg.attn_parallel


def _hybrid_period(cfg: ModelConfig):
    """Detect a periodic hybrid pattern.

    Returns (period, offset) when ``attn_layer_idx`` is exactly one
    attention layer per ``period = n_layer / n_attn`` layers at a fixed
    in-period ``offset`` (config 5: every 8th layer at offset 3); None
    for aperiodic patterns (which take the unrolled path).
    """
    idx = cfg.attn_layer_idx
    n_attn = len(idx)
    if n_attn == 0 or cfg.n_layer % n_attn:
        return None
    p = cfg.n_layer // n_attn
    r = idx[0]
    if not 0 <= r < p:
        return None
    if tuple(idx) != tuple(r + g * p for g in range(n_attn)):
        return None
    return p, r


def _group_mamba_stack(params, cfg: ModelConfig, period: int):
    """(n_mamba, ...) stacked mamba blocks -> (n_attn, period-1, ...)."""
    n_groups = len(cfg.attn_layer_idx)
    return jax.tree.map(
        lambda x: x.reshape((n_groups, period - 1) + x.shape[1:]),
        params["blocks"],
    )


def init_lm_params(key: jax.Array, cfg: ModelConfig) -> dict:
    """Build the full parameter pytree (fp32 master weights)."""
    n = cfg.n_layer
    attn_idx = set(cfg.attn_layer_idx)
    k_emb, k_blocks, k_head = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_blocks, n)

    params = {
        "embedding": cfg.initializer_range
        * jax.random.normal(k_emb, (cfg.vocab_size_padded, cfg.d_model), jnp.float32),
        "norm_f": {"weight": jnp.ones((cfg.d_model,), jnp.float32)},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init_linear(k_head, cfg.d_model, cfg.vocab_size_padded, False)

    if _two_stacks(cfg):
        mamba_keys = [layer_keys[i] for i in range(n) if i not in attn_idx]
        attn_keys = [layer_keys[i] for i in range(n) if i in attn_idx]
        params["blocks"] = jax.vmap(lambda k: _init_block(k, cfg, False))(
            jnp.stack(mamba_keys)
        )
        params["attn_blocks"] = jax.vmap(lambda k: _init_block(k, cfg, True))(
            jnp.stack(attn_keys)
        )
    else:
        params["blocks"] = jax.vmap(lambda k: _init_block(k, cfg, False))(layer_keys)
    return params


def _backbone(
    params: dict,
    cfg: ModelConfig,
    input_ids: jax.Array,
    num_last_tokens: int = 0,
    seq_ctx=None,
):
    """Embedding -> layer stack.  Returns (post-add fp32 stream, aux sum) —
    everything before the final norm + LM head (shared by lm_forward and
    the blocked-CE loss path)."""
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    residual_dtype = jnp.float32 if cfg.residual_in_fp32 else compute_dtype
    hidden = _embed(params, cfg, input_ids)
    # Single-carry form: the layer loop carries ONE post-add fp32 stream
    # instead of the (hidden, residual) pair.  The pair made every remat
    # boundary save the stream twice — stacked bf16 AND fp32 copies per
    # layer, ~2.4 GB of saves on the 280M recipe (round-4 trace); the
    # fp32 add chain and every norm input are bit-identical either way.
    res = hidden.astype(residual_dtype)
    moe = cfg.moe_num_experts > 0
    aux_total = jnp.zeros((), jnp.float32)

    def block(bp, cfg_, res_, attn, sc):
        """post-add stream -> (new stream, aux) — uniform carry shape."""
        out = _block_fwd(bp, cfg_, None, res_, attn, sc)
        if moe:
            h, rs, a = out
        else:
            (h, rs), a = out, jnp.zeros((), jnp.float32)
        return rs + h.astype(rs.dtype), a

    if _two_stacks(cfg) and (per := _hybrid_period(cfg)) is not None:
        # periodic hybrid: scan over supersteps — trace cost O(period)
        p, r = per
        mstack = _group_mamba_stack(params, cfg, p)

        def mbody(carry, bp):
            rs, ax = carry
            rs, a = block(bp, cfg, rs, False, seq_ctx)
            return (rs, ax + a), None

        def abody_(bp, cfg_, rs, ax, attn, sc):
            rs, a = block(bp, cfg_, rs, attn, sc)
            return rs, ax + a

        abody = abody_
        if cfg.remat:
            mbody = _remat(mbody, cfg)
            abody = _remat(abody, cfg, static_argnums=(1, 4, 5))

        def group(carry, xs):
            mblk, ablk = xs
            carry, _ = jax.lax.scan(
                mbody, carry, jax.tree.map(lambda x: x[:r], mblk)
            )
            carry = abody(ablk, cfg, *carry, True, seq_ctx)
            carry, _ = jax.lax.scan(
                mbody, carry, jax.tree.map(lambda x: x[r:], mblk)
            )
            return carry, None

        with jax.named_scope(scopes.LAYERS):
            (res, aux_total), _ = jax.lax.scan(
                group, (res, aux_total), (mstack, params["attn_blocks"])
            )
    elif _two_stacks(cfg):
        attn_idx = set(cfg.attn_layer_idx)
        mi = ai = 0
        for i in range(cfg.n_layer):
            attn = i in attn_idx
            stack = params["attn_blocks"] if attn else params["blocks"]
            j = ai if attn else mi
            body = block
            if cfg.remat:
                body = _remat(body, cfg, static_argnums=(1, 3, 4))
            with jax.named_scope(scopes.LAYERS):
                bp = jax.tree.map(lambda p, j=j: p[j], stack)
                res, a = body(bp, cfg, res, attn, seq_ctx)
            aux_total = aux_total + a
            if attn:
                ai += 1
            else:
                mi += 1
    else:
        if moe:
            def body(carry, bp):
                rs, ax = carry
                rs, a = block(bp, cfg, rs, False, seq_ctx)
                return (rs, ax + a), None

            if cfg.remat:
                body = _remat(body, cfg)
            with jax.named_scope(scopes.LAYERS):
                (res, aux_total), _ = jax.lax.scan(
                    body, (res, aux_total), params["blocks"]
                )
        else:
            def body(rs, bp):
                rs, _ = block(bp, cfg, rs, False, seq_ctx)
                return rs, None

            if cfg.remat:
                body = _remat(body, cfg)
            with jax.named_scope(scopes.LAYERS):
                res, _ = jax.lax.scan(body, res, params["blocks"])

    if num_last_tokens > 0:
        res = res[:, -num_last_tokens:]
    return res, aux_total


def lm_forward(
    params: dict,
    cfg: ModelConfig,
    input_ids: jax.Array,
    num_last_tokens: int = 0,
    seq_ctx=None,
    return_aux: bool = False,
):
    """input_ids (b, t) int32 -> logits (b, t[, num_last_tokens], V) bf16.

    ``return_aux=True`` additionally returns the per-MoE-layer mean of
    the load-balance aux loss (0.0 for dense models) — what lm_loss
    folds in with weight ``cfg.moe_aux_weight``.
    """
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    res, aux_total = _backbone(params, cfg, input_ids, num_last_tokens, seq_ctx)
    logits = _final_logits(params, cfg, None, res).astype(compute_dtype)
    if return_aux:
        n_moe = cfg.n_layer if cfg.moe_num_experts else 1
        return logits, aux_total / n_moe
    return logits


def lm_loss(
    params: dict,
    cfg: ModelConfig,
    input_ids: jax.Array,
    targets: jax.Array,
    seq_ctx=None,
) -> jax.Array:
    """Mean cross-entropy in fp32 (reference model.py:43-46; targets are the
    loader's pre-shifted next tokens, so no internal shift).

    Formulated as ``logsumexp - gathered logit`` rather than materializing
    ``log_softmax`` — the dense (b, t, V) fp32 log-prob tensor (1.6 GB at
    the 280M recipe) never exists; only the two reductions over V do.

    ``cfg.loss_impl="blocked"`` goes further: the LM-head matmul runs
    vocab-block-by-block under an online logsumexp (ops/loss.py), so even
    the (b, t, V) *bf16 logits* tensor (824 MB at the 280M recipe, 3.3 GB
    at the reference's B=32) never exists — forward or backward.
    """
    if cfg.loss_impl == "blocked":
        from mamba_distributed_tpu.ops.loss import blocked_cross_entropy

        res, aux = _backbone(params, cfg, input_ids, seq_ctx=seq_ctx)
        with jax.named_scope(scopes.LM_HEAD_LOSS):
            normed = _final_norm(params, cfg, None, res)
            if cfg.lm_head_multiplier != 1.0:
                # the head's scalar commutes with the product
                normed = normed * cfg.lm_head_multiplier
            ce = blocked_cross_entropy(
                normed,
                _head_matrix(params, cfg),
                targets,
                n_blocks=cfg.loss_vocab_blocks,
                compute_dtype=jnp.dtype(cfg.compute_dtype),
            )
        aux = aux / (cfg.n_layer if cfg.moe_num_experts else 1)
    else:
        logits, aux = lm_forward(
            params, cfg, input_ids, seq_ctx=seq_ctx, return_aux=True
        )
        with jax.named_scope(scopes.LM_HEAD_LOSS):
            lf = logits.astype(jnp.float32)
            lse = jax.nn.logsumexp(lf, axis=-1)
            tgt = jnp.take_along_axis(
                lf, targets[..., None], axis=-1
            )[..., 0]
            ce = jnp.mean(lse - tgt)
    if cfg.moe_num_experts:
        return ce + cfg.moe_aux_weight * aux
    return ce


def lm_loss_pipelined(
    params: dict,
    cfg: ModelConfig,
    input_ids: jax.Array,
    targets: jax.Array,
    mesh,
    axis: str = "pipe",
    batch_axes=None,
) -> jax.Array:
    """``lm_loss`` averaged over grad-accum microbatches, with the layer
    stack pipelined over the mesh's ``axis`` (GPipe).

    The grad-accum microbatches ARE the pipeline microbatches:
    input_ids/targets carry a leading (accum, B, T) axis, embedding and
    LM head run batched over it, and the block stack streams the
    microbatches through ``parallel/pipeline.pipelined_layers`` — whose
    schedule is differentiable (ppermute/scan/where all transpose), so
    one ``jax.grad`` trains through the pipeline.  Uniform stacks
    pipeline per layer; periodic hybrids (config-5 pattern) pipeline per
    *superstep* — each pipeline "layer" is one
    ``[offset mamba] -> attn -> [rest mamba]`` group, so the per-stage
    work stays homogeneous.
    """
    from mamba_distributed_tpu.parallel.pipeline import pipelined_layers

    compute_dtype = jnp.dtype(cfg.compute_dtype)
    residual_dtype = jnp.float32 if cfg.residual_in_fp32 else compute_dtype
    hidden = _embed(params, cfg, input_ids)  # (mb,b,t,d)
    # single-carry post-add stream (see lm_forward)
    res = hidden.astype(residual_dtype)

    def sc_block(bp, res_, attn):
        h, rs = _block_fwd(bp, cfg, None, res_, attn)
        return rs + h.astype(rs.dtype)

    if _two_stacks(cfg):
        per = _hybrid_period(cfg)
        assert per is not None, (
            "pipeline parallelism needs a uniform stack or a periodic hybrid"
        )
        p, r = per
        stacked = (_group_mamba_stack(params, cfg, p), params["attn_blocks"])

        def mbody(carry, bp):
            return sc_block(bp, carry, False), None

        def body(carry, group):
            mblk, ablk = group
            carry, _ = jax.lax.scan(
                mbody, carry, jax.tree.map(lambda x: x[:r], mblk)
            )
            carry = sc_block(ablk, carry, True)
            carry, _ = jax.lax.scan(
                mbody, carry, jax.tree.map(lambda x: x[r:], mblk)
            )
            return carry
    else:
        stacked = params["blocks"]

        def body(carry, bp):
            return sc_block(bp, carry, False)

    if cfg.remat:
        body = _remat(body, cfg)
    res = pipelined_layers(
        body, stacked, res, mesh, axis=axis,
        batch_axes=batch_axes,
    )
    lf = _final_logits(params, cfg, None, res)
    lse = jax.nn.logsumexp(lf, axis=-1)
    tgt = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt)


def count_params(params) -> int:
    return sum(int(p.size) for p in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Recurrent decode (O(1) per token) — used by inference/generate.py
# ---------------------------------------------------------------------------


def lm_prefill(params: dict, cfg: ModelConfig, input_ids: jax.Array,
               max_len: int = 0, token_mask: jax.Array | None = None):
    """Parallel prefill: one full-sequence forward that also returns the
    per-layer decode state (conv cache, SSM state, attention KV caches
    padded to ``max_len``).  The sequential per-token prefill this replaces
    is what the reference effectively did by re-running the prefix
    (SURVEY.md §3.3).  Shares ``_block_fwd`` with lm_forward.

    ``token_mask`` (b, t) {0,1} marks LEFT-padded bucketed prompts
    (inference/bucketing.py): pad positions contribute nothing to the
    conv/SSM state, so the returned state matches the unpadded
    prefill's — the conv cache bit-exactly, the SSM state up to
    chunk-regrouping rounding (~1e-7 fp32).  Pure-SSM stacks only —
    attention layers reject it (_block_fwd).

    Returns (last_logits (b, V) fp32, state) — state feeds lm_step.
    """
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    b, t = input_ids.shape
    if cfg.attn_layer_idx and max_len <= t:
        raise ValueError(
            f"hybrid prefill needs KV capacity beyond the prompt: "
            f"max_len={max_len} <= prompt length {t}"
        )
    hidden = _embed(params, cfg, input_ids)
    residual = None

    def to_pages(state):
        # raw full-sequence (k, v) -> identity-paged decode cache with
        # ``max_len`` capacity (the shared page_table/lengths meta is
        # attached once, below)
        k, v = state
        return pack_attention_pages(cfg, k, v, max_len)

    if cfg.attn_layer_idx and token_mask is not None:
        raise ValueError(
            "token_mask prefill is SSM-only (full-sequence attention would "
            "attend the pad keys); hybrid bucketed prompts go through the "
            "chunk step (serving/prefill.py) instead"
        )

    if _two_stacks(cfg) and (per := _hybrid_period(cfg)) is not None:
        # periodic hybrid: superstep scan mirroring lm_forward's
        p, r = per
        residual = jnp.zeros_like(
            hidden, dtype=jnp.float32 if cfg.residual_in_fp32 else compute_dtype
        )
        mstack = _group_mamba_stack(params, cfg, p)

        def mbody(carry, bp):
            h, rs = carry
            h, rs, (st, _), _ = _block_fwd(bp, cfg, h, rs, return_state=True)
            return (h, rs), st

        def group(carry, xs):
            mblk, ablk = xs
            with jax.named_scope(scopes.LAYERS):
                carry, st_pre = jax.lax.scan(
                    mbody, carry, jax.tree.map(lambda x: x[:r], mblk)
                )
            hidden, residual, (_, a_st), _ = _block_fwd(
                ablk, cfg, *carry, True, return_state=True
            )
            with jax.named_scope(scopes.LAYERS):
                carry, st_post = jax.lax.scan(
                    mbody, (hidden, residual),
                    jax.tree.map(lambda x: x[r:], mblk),
                )
            m_st = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b], axis=0), st_pre, st_post
            )
            return carry, (m_st, to_pages(a_st))

        with jax.named_scope(scopes.ATTN_LAYERS):
            (hidden, residual), (m_states, a_states) = jax.lax.scan(
                group, (hidden, residual), (mstack, params["attn_blocks"])
            )
        state = {
            # (n_attn, period-1, ...) -> (n_mamba, ...), global layer order
            "blocks": jax.tree.map(
                lambda x: x.reshape((-1,) + x.shape[2:]), m_states
            ),
            "attn_blocks": a_states,
            "attn_meta": (
                attention_page_meta(cfg, b, max_len)[0],
                jnp.full((b,), t, jnp.int32),
            ),
        }
    elif _two_stacks(cfg):
        attn_idx = set(cfg.attn_layer_idx)
        mi = ai = 0
        m_states, a_states = [], []
        for i in range(cfg.n_layer):
            attn = i in attn_idx
            stack = params["attn_blocks"] if attn else params["blocks"]
            with jax.named_scope(scopes.ATTN_LAYERS):
                bp = jax.tree.map(
                    lambda p, j=(ai if attn else mi): p[j], stack
                )
                hidden, residual, (m_st, a_st), _ = _block_fwd(
                    bp, cfg, hidden, residual, attn, return_state=True
                )
            if attn:
                a_states.append(to_pages(a_st))
                ai += 1
            else:
                m_states.append(m_st)
                mi += 1
        stack = lambda sts: jax.tree.map(lambda *xs: jnp.stack(xs), *sts)
        state = {
            "blocks": stack(m_states),
            "attn_blocks": stack(a_states),
            "attn_meta": (
                attention_page_meta(cfg, b, max_len)[0],
                jnp.full((b,), t, jnp.int32),
            ),
        }
    else:
        # one stack: Mamba blocks, or parallel blocks (a layer then hands
        # back both states, and the scan is the pool's)
        residual = jnp.zeros_like(
            hidden, dtype=jnp.float32 if cfg.residual_in_fp32 else compute_dtype
        )

        def body(carry, bp):
            hidden, residual = carry
            hidden, residual, (m_st, a_st), _ = _block_fwd(
                bp, cfg, hidden, residual, return_state=True,
                token_mask=token_mask,
            )
            if cfg.attn_parallel:
                return (hidden, residual), (m_st, to_pages(a_st))
            return (hidden, residual), m_st

        with jax.named_scope(
            scopes.ATTN_LAYERS if cfg.attn_parallel else scopes.LAYERS
        ):
            (hidden, residual), state_blocks = jax.lax.scan(
                body, (hidden, residual), params["blocks"]
            )
        if cfg.attn_parallel:
            state = {
                "blocks": state_blocks[0],
                "attn_blocks": state_blocks[1],
                "attn_meta": (
                    attention_page_meta(cfg, b, max_len)[0],
                    jnp.full((b,), t, jnp.int32),
                ),
            }
        else:
            state = {"blocks": state_blocks}

    logits = _final_logits(params, cfg, hidden[:, -1:], residual[:, -1:])
    return logits[:, 0].astype(jnp.float32), state


def lm_prefill_chunk(params: dict, cfg: ModelConfig, input_ids: jax.Array,
                     state, token_mask: jax.Array | None = None,
                     return_load: bool = False):
    """Resumable prefill: one chunk of a prompt, carries threaded through.

    The chunked-prefill workhorse (serving/prefill.py): identical to the
    pure-SSM branch of ``lm_prefill`` except every layer's mixer starts
    from ``state`` — the ``{"blocks": (conv (L, b, ...), ssm (L, b, ...))}``
    pytree a previous chunk (or ``init_lm_state``) produced — so a long
    prompt runs as a sequence of fixed-shape chunk calls: one compiled
    shape total, and the serving engine can interleave chunks with
    decode ticks.

    Chunk-split equivalence vs one ``lm_prefill`` over the concatenated
    sequence: everything outside the mixers is per-position; the conv
    carry is the literal trailing inputs (bit-exact across a split); the
    SSM carry enters the next chunk's state passing as mathematically
    the same recurrence with re-associated fp32 sums (~1e-6 — same
    class of noise as the pow2 bucketing's pad-shifted chunk boundaries;
    tests/test_prefill.py pins both the exact and the tolerance parts).
    Exact token parity between the engine and ``generate()`` therefore
    comes from both sides running THIS function over identical chunks,
    not from chunked == one-shot.

    Hybrid stacks resume attention layers against the PAGED KV cache in
    ``state["attn_blocks"]``/``state["attn_meta"]`` — each chunk writes
    its real tokens' K/V into the row's pages at [lengths, lengths +
    n_real) and attends over the page view (models/attention.
    attention_mixer_chunk), so a hybrid prompt's pages fill as chunks
    land and the serving engine can interleave them with decode ticks.
    The page pool rides the layer loop's carry whole and comes back
    whole (``_hybrid_layers``): under the chunk step's donation the
    pages are written in place.

    Returns (last_logits (b, V) fp32, new state) — same contract as
    ``lm_prefill``; with ``return_load`` also the expert layers' load
    (``_moe_mlp``: (held + 1,) int32, of the positions ``token_mask``
    marks) summed over the layers; None for a dense model.
    """
    hidden, residual, new_state, load = _chunk_backbone(
        params, cfg, input_ids, state, token_mask
    )
    logits = _final_logits(params, cfg, hidden[:, -1:], residual[:, -1:])
    if return_load:
        return logits[:, 0].astype(jnp.float32), new_state, load
    return logits[:, 0].astype(jnp.float32), new_state


def lm_verify_chunk(params: dict, cfg: ModelConfig, input_ids: jax.Array,
                    state, token_mask: jax.Array | None = None):
    """Speculative-decoding VERIFY step: the chunk machinery of
    ``lm_prefill_chunk`` (identical carry threading, identical paged KV
    chunk write for hybrids) but returning the logits of EVERY position
    — ``(logits (b, c, V) fp32, new state)`` where ``logits[:, i]``
    scores the token AFTER ``input_ids[:, i]``.

    This is the whole trick (serving/spec_decode.py): one launch reads
    the weights ONCE and prices all ``c = K+1`` positions of a drafted
    continuation, where the decode tick would pay one full weight read
    per token.  The caller compares ``argmax(logits[:, i-1])`` against
    the fed draft at ``i`` to find the longest correct prefix, commits
    it, and rolls back the carries on a rejection (the returned state
    reflects ALL ``c`` fed tokens, so it is only committable when every
    one of them verified — the pending-token scheme in
    serving/spec_decode.py keeps that an all-or-nothing choice).

    Hybrid note: the chunk's K/V page writes land at ``[lengths,
    lengths + n_real)`` exactly like a prefill chunk; on rollback the
    caller simply does not advance its ``lengths`` mirror, so the
    written cells are dead-by-``lengths`` and the next verify rewrites
    them — the same invariant the ragged kernels already honor for
    masked rows."""
    hidden, residual, new_state, _ = _chunk_backbone(
        params, cfg, input_ids, state, token_mask
    )
    logits = _final_logits(params, cfg, hidden, residual)
    return logits.astype(jnp.float32), new_state


def _layer_of(stacked, i):
    """Layer ``i``'s leaves of a layer-stacked state (``i`` an int, or a
    traced scalar under a scan)."""
    return jax.tree.map(
        lambda s: jax.lax.dynamic_index_in_dim(s, i, 0, keepdims=False),
        stacked,
    )


def _with_layer(stacked, i, new):
    """``stacked`` with layer ``i``'s leaves replaced by ``new``: on a
    buffer the caller carries through its loop and donates, an in-place
    write of that layer's rows."""
    return jax.tree.map(
        lambda s, n: jax.lax.dynamic_update_index_in_dim(s, n, i, 0),
        stacked, new,
    )


def _zero_load(cfg: ModelConfig):
    """The expert load a layer loop starts from: zeros (``_moe_mlp``), or
    None for a dense model (no leaf in the loop's carry, so its program is
    what it was)."""
    if not cfg.moe_num_experts:
        return None
    return jnp.zeros((cfg.moe_held[1] + 1,), jnp.int32)


def _add_load(load, layer_load):
    return None if load is None else load + layer_load


def _hybrid_layers(params: dict, cfg: ModelConfig, hidden, residual, state,
                   block):
    """The layer loop of the two stateful steps (``lm_step``,
    ``_chunk_backbone``) for every stack that has attention: a two-stack
    hybrid, periodic (scanned by group) or not (unrolled), and a stack of
    parallel blocks (one scan).

    ``block(bp, h, rs, st, kv, a) -> (h, rs, st', kv', load)`` runs one
    block: ``st`` is its layer's ``(conv, ssm)`` state, None for an
    attention block of a two-stack model; ``kv`` the WHOLE page pool and
    ``a`` its layer's index into it, both None for a Mamba block, which
    hands None back.  A parallel block (``cfg.attn_parallel``) gets and
    returns both.  ``load`` is the block's expert load (``_moe_mlp``), None
    for a dense block; the loop sums it over the layers in its carry, where
    None adds no leaf.

    Both stacked states, ``state["blocks"]`` and ``state["attn_blocks"]``,
    ride the loop's CARRY, a layer addressed by its index (traced in the
    scan, an int when unrolled): a Mamba layer's rows are sliced out,
    stepped and written back where they were, the page pool is never
    sliced at all.  So a caller that carries and donates the state (the
    serving tick, the chunk step) has ONE buffer of each from entry to
    exit.  Scanned in and out, the pool would be sliced a layer at a
    time, stacked into a second pool and copied back into the caller's
    carry, on every sub-step and chunk (2 x 537 MB of pages at the
    benchmark's hybrid, five eighths of its device time).

    Returns (hidden, residual, blocks', attn_blocks', load).
    """
    blocks, akv = state["blocks"], state["attn_blocks"]
    load = _zero_load(cfg)

    if cfg.attn_parallel:
        def layer(carry, xs):
            h, rs, blocks, akv, load = carry
            bp, i = xs
            h, rs, st, akv, ld = block(
                bp, h, rs, _layer_of(blocks, i), akv, i)
            return (h, rs, _with_layer(blocks, i, st), akv,
                    _add_load(load, ld)), None

        with jax.named_scope(scopes.ATTN_LAYERS):
            (hidden, residual, blocks, akv, load), _ = jax.lax.scan(
                layer, (hidden, residual, blocks, akv, load),
                (params["blocks"], jnp.arange(cfg.n_layer)),
            )
        return hidden, residual, blocks, akv, load

    def mamba(carry, xs):
        h, rs, blocks, load = carry
        bp, i = xs
        h, rs, st, _, ld = block(bp, h, rs, _layer_of(blocks, i), None, None)
        return (h, rs, _with_layer(blocks, i, st), _add_load(load, ld)), None

    if (per := _hybrid_period(cfg)) is not None:
        p, r = per

        # Both loops run over layer INDICES and read a layer's weights out
        # of the whole stack where it stands, as they read its state: a
        # stack sliced by group, and a group's by the attention layer's
        # place in it, would be copied on every sub-step and chunk (each
        # slice is a buffer of its own: 4.5 GB of a routed model's experts).
        def mamba_at(carry, i):
            return mamba(carry, (_layer_of(params["blocks"], i), i))

        def group(carry, g):
            h, rs, blocks, akv, load = carry
            first = g * (p - 1)  # the group's first Mamba layer
            with jax.named_scope(scopes.LAYERS):
                (h, rs, blocks, load), _ = jax.lax.scan(
                    mamba_at, (h, rs, blocks, load), first + jnp.arange(r))
            h, rs, _, akv, ld = block(
                _layer_of(params["attn_blocks"], g), h, rs, None, akv, g)
            load = _add_load(load, ld)
            with jax.named_scope(scopes.LAYERS):
                (h, rs, blocks, load), _ = jax.lax.scan(
                    mamba_at, (h, rs, blocks, load),
                    first + r + jnp.arange(p - 1 - r))
            return (h, rs, blocks, akv, load), None

        with jax.named_scope(scopes.ATTN_LAYERS):
            (hidden, residual, blocks, akv, load), _ = jax.lax.scan(
                group, (hidden, residual, blocks, akv, load),
                jnp.arange(len(cfg.attn_layer_idx)),
            )
    else:
        attn_idx = set(cfg.attn_layer_idx)
        mi = ai = 0
        with jax.named_scope(scopes.ATTN_LAYERS):
            for i in range(cfg.n_layer):
                if i in attn_idx:
                    bp = jax.tree.map(
                        lambda p_, j=ai: p_[j], params["attn_blocks"]
                    )
                    hidden, residual, _, akv, ld = block(
                        bp, hidden, residual, None, akv, ai
                    )
                    load = _add_load(load, ld)
                    ai += 1
                else:
                    bp = jax.tree.map(lambda p_, j=mi: p_[j], params["blocks"])
                    (hidden, residual, blocks, load), _ = mamba(
                        (hidden, residual, blocks, load), (bp, mi)
                    )
                    mi += 1
    return hidden, residual, blocks, akv, load


def _chunk_backbone(params: dict, cfg: ModelConfig, input_ids: jax.Array,
                    state, token_mask: jax.Array | None = None):
    """Shared body of ``lm_prefill_chunk``/``lm_verify_chunk``: embed ->
    carry-threaded layer stack -> (hidden, residual, new state, load).  One
    implementation so the prefill and verify paths cannot diverge.
    ``load`` is the expert layers' (``_moe_mlp``, of the positions
    ``token_mask`` marks), summed over the layers; None for a dense model."""
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    hidden = _embed(params, cfg, input_ids)
    residual = jnp.zeros_like(
        hidden, dtype=jnp.float32 if cfg.residual_in_fp32 else compute_dtype
    )

    def body(carry, xs):
        hidden, residual, load = carry
        bp, st = xs
        hidden, residual, (new_st, _), ld = _block_fwd(
            bp, cfg, hidden, residual, return_state=True,
            token_mask=token_mask, initial_state=(st, None),
        )
        return (hidden, residual, _add_load(load, ld)), new_st

    if cfg.attn_layer_idx:
        tbl, lengths = state["attn_meta"]
        b, c = input_ids.shape
        if token_mask is None:
            n_real = jnp.full((b,), c, jnp.int32)
        else:
            n_real = jnp.sum(
                (token_mask > 0.5).astype(jnp.int32), axis=1
            )

        def block(bp, h, rs, st, akv, a):
            paged = None if a is None else (akv, a, tbl, lengths)
            h, rs, new, ld = _block_fwd(
                bp, cfg, h, rs, st is None, return_state=True,
                token_mask=token_mask, initial_state=(st, paged),
            )
            return (h, rs, *new, ld)

        hidden, residual, new_blocks, new_a, load = _hybrid_layers(
            params, cfg, hidden, residual, state, block
        )
        return hidden, residual, {
            "blocks": new_blocks,
            "attn_blocks": new_a,
            "attn_meta": (tbl, lengths + n_real),
        }, load

    with jax.named_scope(scopes.LAYERS):
        (hidden, residual, load), state_blocks = jax.lax.scan(
            body, (hidden, residual, _zero_load(cfg)),
            (params["blocks"], state["blocks"])
        )
    return hidden, residual, {"blocks": state_blocks}, load


def init_lm_blocks_state(cfg: ModelConfig, batch: int):
    """Layer-stacked conv+SSM decode states for the MAMBA layers only —
    what the serving slot pool's per-slot writes cover (the paged
    attention KV lives in the shared page pool, not per-slot rows)."""
    init_mix = init_mamba2_state if cfg.ssm_layer == "mamba2" else init_mamba1_state
    n = cfg.n_mamba_layers
    cs, ss = init_mix(cfg, batch)
    return (
        jnp.tile(cs[None], (n,) + (1,) * cs.ndim),
        jnp.tile(ss[None], (n,) + (1,) * ss.ndim),
    )


def init_lm_state(cfg: ModelConfig, batch: int, max_len: int = 0):
    """Per-layer decode states, layer-stacked to mirror the param layout.

    Hybrid stacks additionally carry the paged attention KV cache:
    per-layer page pools under ``"attn_blocks"`` plus the layer-shared
    ``"attn_meta" = (page_table (b, W), lengths (b,))`` (every attention
    layer caches the same positions, so one table serves them all).
    ``max_len`` sizes the per-row page budget."""
    if cfg.attn_layer_idx:
        n_attn = len(cfg.attn_layer_idx)
        attn_states = [
            init_attention_state(cfg, batch, max_len) for _ in range(n_attn)
        ]
        stack = lambda states: jax.tree.map(lambda *xs: jnp.stack(xs), *states)
        return {
            "blocks": init_lm_blocks_state(cfg, batch),
            "attn_blocks": stack(attn_states),
            "attn_meta": attention_page_meta(cfg, batch, max_len),
        }
    return {"blocks": init_lm_blocks_state(cfg, batch)}


def _block_step(bp, cfg: ModelConfig, hidden, residual, st, akv=None,
                attn_ctx=None, state_mask=None, row_mask=None):
    """One decode-step block (shared by the scan and unrolled paths) ->
    (hidden, residual, st', akv', load); ``load`` is the expert layer's
    (``_moe_mlp``, of the rows ``row_mask`` marks), None for a dense block.  ``st`` is the block's ``(conv, ssm)``
    state, None for an attention block of a two-stack model; ``akv`` the
    whole page pool (it comes back whole) and ``attn_ctx = (page_table,
    lengths, write_mask, layer)`` the layer-shared paged-KV metadata plus
    this layer's index into the pool, both None for a Mamba block.  A
    parallel block (``cfg.attn_parallel``) is handed both and uses both.
    ``state_mask`` is ``lm_step``'s, for the conv + SSM carry."""
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    normed, residual = add_rms_norm(
        hidden, residual, bp["norm"]["weight"], cfg.norm_eps,
    )
    if cfg.attn_parallel:
        page_table, lengths, write_mask, layer = attn_ctx
        with jax.named_scope(scopes.SSM_BRANCH):
            ssm_out, st = mamba2_mixer_step(
                bp["mixer"], cfg, normed, *st, state_mask=state_mask
            )
        with jax.named_scope(scopes.ATTN_BRANCH):
            attn_out, akv = attention_mixer_step(
                bp["attn"], cfg, _attn_input(cfg, normed), akv, layer,
                page_table, lengths, write_mask=write_mask,
            )
        hidden = _add_branches(cfg, ssm_out, attn_out)
    elif st is None:
        page_table, lengths, write_mask, layer = attn_ctx
        hidden, akv = attention_mixer_step(
            bp["mixer"], cfg, normed, akv, layer, page_table, lengths,
            write_mask=write_mask,
        )
    else:
        mix_step = (
            mamba2_mixer_step if cfg.ssm_layer == "mamba2" else mamba1_mixer_step
        )
        hidden, st = mix_step(
            bp["mixer"], cfg, normed, *st, state_mask=state_mask
        )
    hidden = _scale_residual(cfg, hidden)
    load = None
    if cfg.d_intermediate > 0:
        normed, residual = add_rms_norm(
            hidden, residual, bp["norm2"]["weight"], cfg.norm_eps,
        )
        if cfg.moe_num_experts:
            hidden, _, load = _expert_layer(bp, cfg, normed, compute_dtype,
                                            row_mask)
        else:
            hidden = _gated_mlp(bp["mlp"], normed, compute_dtype,
                                cfg.mlp_multipliers)
        hidden = _scale_residual(cfg, hidden)
    return hidden, residual, st, akv, load


def lm_step(params: dict, cfg: ModelConfig, state, token: jax.Array,
            write_mask: jax.Array | None = None, pipeline=None,
            state_mask: jax.Array | None = None, return_load: bool = False):
    """One decode step.  token (b,) int32 -> (logits (b, V), new state);
    with ``return_load`` also the expert layers' load (``_moe_mlp``:
    (held + 1,) int32, of the rows ``write_mask`` marks) summed over the
    layers; None for a dense model and under ``pipeline``.

    ``write_mask`` (b,) bool marks the rows that are served: the expert
    load counts them alone, and on hybrid stacks they are the rows whose paged
    attention KV may be written this step; masked rows' writes land in
    the trash page and their ``lengths`` freeze — how the serving tick
    keeps dead/empty/prefilling slots from touching live pages while
    still computing the whole batch in one trace.  ``None`` (generate's
    decode loop) writes every row.

    ``state_mask`` (b,) bool marks rows whose conv + SSM carry
    (``state["blocks"]``) advances; the others get theirs back bit for
    bit, out of the update's own write (ops/ssd.ssd_state_update), so no
    caller has to select over the stacked state afterwards.  The serving
    tick passes ``~prefilling``: a slot parked mid-chunked-prefill holds a
    real scan carry that the next chunk resumes from.  It is NOT ``live``
    like ``write_mask``: an empty or finished slot's rows are garbage the
    next insert overwrites and may advance freely, while a stray KV write
    could land in a page that now belongs to someone else.  The held
    rows' logits mean nothing (the caller keeps its own).  ``None``
    (generate's decode loop, the drafter) advances every row, with the
    values a mask of all True gives.

    ``pipeline`` (pure-SSM stacks only) is ``(mesh, n_micro)``: the
    layer scan runs as a GPipe-microbatched schedule over the 3-D
    serving mesh's ``stage`` axis instead of a local ``lax.scan`` —
    ``n_micro`` contiguous lane blocks of the batch flow through the
    stage-resident layer groups with ppermute handoffs
    (parallel/pipeline.pipelined_decode_layers; the serving tick's
    microbatched launch).  Bitwise identical to ``pipeline=None``:
    each lane's per-layer op sequence is unchanged, only the
    (layer-group, lane-block) execution order moves.  ``None`` (every
    non-pipelined caller) is the exact status quo.

    The stacked state (``state["blocks"]``; hybrids: the KV page pool
    ``state["attn_blocks"]`` too) rides every layer loop's carry and each
    layer is addressed by its index, so a caller that carries and donates
    the state (the serving tick) has one buffer of each from entry to
    exit: the new state is the old one written in place.
    """
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    hidden = _embed(params, cfg, token)
    residual = None

    if cfg.attn_layer_idx:
        tbl, lengths = state["attn_meta"]
        adv = (
            jnp.ones_like(lengths) if write_mask is None
            else write_mask.astype(lengths.dtype)
        )
        residual = jnp.zeros_like(hidden, dtype=jnp.float32)

        def block(bp, h, rs, st, akv, a):
            ctx = None if a is None else (tbl, lengths, write_mask, a)
            return _block_step(bp, cfg, h, rs, st, akv, attn_ctx=ctx,
                               state_mask=state_mask, row_mask=write_mask)

        hidden, residual, new_blocks, new_a, load = _hybrid_layers(
            params, cfg, hidden, residual, state, block
        )
        new_state = {
            "blocks": new_blocks,
            "attn_blocks": new_a,
            "attn_meta": (tbl, lengths + adv),
        }
    else:
        residual = jnp.zeros_like(hidden, dtype=jnp.float32)
        load = None
        if pipeline is not None:
            from mamba_distributed_tpu.parallel.pipeline import (
                pipelined_decode_layers,
            )

            mesh, n_micro = pipeline

            # the mask is one more per-lane leaf of the activations (None:
            # no leaf), so each microbatch's rows travel the stages with it
            def pbody(act, bp, st):
                h, rs, mask = act
                h, rs, st, _, _ = _block_step(bp, cfg, h, rs, st,
                                              state_mask=mask)
                return (h, rs, mask), st

            (hidden, residual, _), new_blocks = pipelined_decode_layers(
                pbody, params["blocks"], state["blocks"],
                (hidden, residual, state_mask), mesh, n_micro=n_micro,
            )
        else:
            # the stacked state rides the layer loop's CARRY: layer i's
            # rows are sliced out, stepped and written back at i, so a
            # caller that carries and donates the pool (the serving tick)
            # updates one buffer in place from entry to exit, where scanned
            # inputs and outputs would be two pool-sized buffers
            def cbody(carry, xs):
                h, rs, blocks, load = carry
                bp, i = xs
                h, rs, st, _, ld = _block_step(
                    bp, cfg, h, rs, _layer_of(blocks, i),
                    state_mask=state_mask, row_mask=write_mask)
                return (h, rs, _with_layer(blocks, i, st),
                        _add_load(load, ld)), None

            with jax.named_scope(scopes.LAYERS):
                (hidden, residual, new_blocks, load), _ = jax.lax.scan(
                    cbody, (hidden, residual, state["blocks"],
                            _zero_load(cfg)),
                    (params["blocks"], jnp.arange(cfg.n_layer)),
                )
        new_state = {"blocks": new_blocks}

    with jax.named_scope(scopes.LM_HEAD_LOSS):
        normed, _ = add_rms_norm(
            hidden, residual, params["norm_f"]["weight"], cfg.norm_eps
        )
        logits = _head_logits(params, cfg, normed)
    if return_load:
        return logits.astype(jnp.float32), new_state, load
    return logits.astype(jnp.float32), new_state
