"""Sequence/context parallelism for the SSD path (BASELINE config 4).

The SSM analogue of ring attention (SURVEY.md §5 long-context plan): the
sequence axis is sharded over the mesh's ``seq`` axis; each device runs
the chunked SSD on its local tokens, and only the tiny (b, h, p, n)
boundary states cross devices — O(d_state) traffic instead of O(T).

Mechanics (explicit `shard_map`, because the state recurrence has a
direction XLA's sharding propagation can't infer):

  * conv halo: each device ppermutes its last (width-1) inputs to the next
    device, which uses them as ``initial_state`` — exactly the decode-cache
    hook `ops/conv.py` exposes.
  * SSD state passing: each device computes its local per-chunk states and
    a (decay, final_state) summary; an exclusive prefix scan over the seq
    axis (log2(S) distance-doubling ppermute rounds, O(d_state) traffic
    each) hands every device its incoming state, and the local associative
    state pass re-runs seeded with it.

Both transforms are exact: sharded output == single-device output to fp32
tolerance (pinned by tests/test_seq_parallel.py).

Compute/communication overlap (SURVEY §7 hard-part 3): the expensive
intra-chunk work — the Gram/decay matmuls behind ``y_diag`` and the
off-diagonal context — has no data dependence on the cross-device state
exchange (only the cheap final ``combine_chunk_outputs`` consumes both),
so the XLA scheduler is free to run the ppermute chain concurrently with
the local matmuls; nothing in the program order forces the exchange onto
the critical path.  Whether the scheduler actually hides the (tiny,
O(d_state)) exchange is a hardware-profile question — measure it in a
device trace of a seq-sharded config (the exposed collective time, as
``benchmark/readers/trace_exposed_share.py`` reads it) before tuning
further.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from mamba_distributed_tpu.ops.conv import causal_conv1d
from mamba_distributed_tpu.ops.ssd import (
    chunk_local,
    combine_chunk_outputs,
    cumsum_mxu,
    state_passing,
)


@dataclasses.dataclass(frozen=True)
class SeqContext:
    """Carries the mesh and axis names the sequence-sharded ops run over.

    ``batch_axes`` must match how the caller shards the batch dimension
    (the trainer's batch sharding: ('data', 'fsdp')).
    """

    mesh: Mesh
    axis: str = "seq"
    batch_axes: tuple[str, ...] = ("data", "fsdp")

    @property
    def size(self) -> int:
        return self.mesh.shape[self.axis]


def _shifted(ctx: SeqContext, x: jax.Array) -> jax.Array:
    """Value from the previous seq rank (zeros into rank 0)."""
    n = ctx.size
    perm = [(i, i + 1) for i in range(n - 1)]
    return jax.lax.ppermute(x, ctx.axis, perm)


def sp_conv1d(
    ctx: SeqContext,
    x: jax.Array,
    weight: jax.Array,
    bias: jax.Array | None,
    activation: str | None = "silu",
):
    """Causal depthwise conv with a (width-1)-token halo exchange.

    x (b, t_global, d) with t sharded over ``ctx.axis``.
    Returns (y, None) — the decode conv state is meaningless under SP.
    """
    width = weight.shape[1]
    bat = P(ctx.batch_axes, ctx.axis, None)
    has_bias = bias is not None

    def local(x_l, w, *rest):
        b = rest[0] if has_bias else None
        halo = None
        if width > 1:  # width=1 needs no halo (and -(width-1) would slice badly)
            assert x_l.shape[1] >= width - 1, (
                f"local sequence shard ({x_l.shape[1]}) shorter than the "
                f"conv halo ({width - 1})"
            )
            halo = _shifted(ctx, x_l[:, -(width - 1) :, :])
        return causal_conv1d(x_l, w, b, activation=activation, initial_state=halo)

    in_specs = (bat, P(None, None)) + ((P(None),) if has_bias else ())
    fn = jax.shard_map(
        local, mesh=ctx.mesh, in_specs=in_specs, out_specs=bat, check_vma=False
    )
    args = (x, weight) + ((bias,) if has_bias else ())
    return fn(*args), None


def _seeded_correction(dt, A, C, s_in, chunk_size, compute_dtype):
    """Off-diagonal contribution of a shard's incoming state.

    The seeded SSD output is *linear* in the incoming state: chunk c adds
    ``diag(e^{a}) C @ (prefix_c * s_in)^T`` where ``prefix_c`` is the
    product of the chunk decays before c.  Computing the seed as a
    correction on top of the *unseeded* forward keeps the intra-chunk
    work (Pallas kernels) to a single pass, with the cross-shard state
    dependency confined to this cheap O(t*n*p) einsum.
    """
    from mamba_distributed_tpu.ops.scan import _divisor_chunk

    b, t, g, n = C.shape
    h = dt.shape[-1]
    l = _divisor_chunk(t, chunk_size)
    nc = t // l
    hpg = h // g
    p = s_in.shape[2]

    dA = (dt.astype(jnp.float32) * A.astype(jnp.float32)).reshape(b, nc, l, h)
    a_cum = cumsum_mxu(dA, axis=2)                   # in-chunk log-decay
    chunk_sum = a_cum[:, :, -1, :]                   # (b, nc, h)
    # prod of chunk decays BEFORE chunk c (exclusive prefix)
    prefix = jnp.exp(cumsum_mxu(chunk_sum, axis=1) - chunk_sum)
    e_a = jnp.exp(a_cum)                             # (b, nc, l, h)

    s_eff = s_in.astype(jnp.float32)[:, None] * prefix[..., None, None]
    s_eff = s_eff.reshape(b, nc, g, hpg, p, n)       # heads grouped: i -> (i//hpg, i%hpg)
    C_r = C.reshape(b, nc, l, g, n)
    corr = jnp.einsum(
        "bclgn,bcgqpn->bclgqp",
        C_r.astype(compute_dtype), s_eff.astype(compute_dtype),
        preferred_element_type=jnp.float32,
    )
    corr = corr * e_a.reshape(b, nc, l, g, hpg)[..., None]
    return corr.reshape(b, t, h, p)


def sp_ssd(
    ctx: SeqContext,
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    B: jax.Array,
    C: jax.Array,
    chunk_size: int,
    D: jax.Array | None = None,
    compute_dtype=jnp.bfloat16,
    ssm_impl: str = "xla",
):
    """Sequence-sharded chunked SSD.

    Shapes as ops/ssd.ssd_chunked: x (b, t, h, p), dt (b, t, h),
    B/C (b, t, g, n), with t sharded over ``ctx.axis``.
    Returns (y, None) — the final state stays on the last shard.

    ``ssm_impl="pallas"`` runs each shard's intra-chunk compute through
    the fused VMEM kernels (ops/pallas/ssd_kernels.py, including their
    Pallas backward via the seeded custom_vjp); only the O(d_state)
    cross-shard state exchange stays shard_map/ppermute.  BASELINE
    config 4 (2.8B, seq 8192) is exactly where this matters.
    """
    from mamba_distributed_tpu.ops.scan import _divisor_chunk

    bat3 = P(ctx.batch_axes, ctx.axis, None)
    bat4 = P(ctx.batch_axes, ctx.axis, None, None)
    has_D = D is not None

    def local(x_l, dt_l, A_, B_l, C_l, *rest):
        D_ = rest[0] if has_D else None
        b, t_l, h, p = x_l.shape
        l = _divisor_chunk(t_l, chunk_size)
        y_diag, states, chunk_decay, off_ctx = chunk_local(
            x_l, dt_l, A_, B_l, C_l, l, compute_dtype
        )
        # local pass to get this shard's summary, then combine across ranks
        _, final_local = state_passing(states, chunk_decay)
        decay_total = jnp.prod(chunk_decay, axis=1)  # (b, h)
        s_in = _incoming_state(ctx, decay_total, final_local)  # (b, h, p, n)

        # local pass seeded with the incoming state, then the shared
        # output assembly (ops/ssd.combine_chunk_outputs)
        prev_states, _ = state_passing(states, chunk_decay, initial_state=s_in)
        return combine_chunk_outputs(
            y_diag, off_ctx, prev_states, x_l, D_, compute_dtype
        )

    def local_pallas(x_l, dt_l, A_, B_l, C_l, *rest):
        from mamba_distributed_tpu.ops.pallas import ssd_chunked_pallas

        D_ = rest[0] if has_D else None
        # one unseeded Pallas pass gives both the local output and the
        # shard summary; the incoming-state contribution is added as the
        # linear correction (see _seeded_correction)
        y0, final_local = ssd_chunked_pallas(
            x_l, dt_l, A_, B_l, C_l, chunk_size=chunk_size, D=D_,
            return_final_state=True, compute_dtype=compute_dtype,
        )
        decay_total = jnp.exp(
            jnp.einsum(
                "bth,h->bh",
                dt_l.astype(jnp.float32), A_.astype(jnp.float32),
            )
        )
        s_in = _incoming_state(ctx, decay_total, final_local)
        corr = _seeded_correction(dt_l, A_, C_l, s_in, chunk_size, compute_dtype)
        return (y0.astype(jnp.float32) + corr).astype(y0.dtype)

    in_specs = (bat4, bat3, P(None), bat4, bat4)
    if has_D:
        in_specs += (P(None, None) if D.ndim == 2 else P(None),)
    fn = jax.shard_map(
        local_pallas if ssm_impl == "pallas" else local,
        mesh=ctx.mesh, in_specs=in_specs, out_specs=bat4, check_vma=False,
    )
    args = (x, dt, A, B, C) + ((D,) if has_D else ())
    return fn(*args), None


def _incoming_state(ctx: SeqContext, decay_total, final_local):
    """Combine per-rank (decay, final-state) summaries into each rank's
    incoming state: sum over ranks j < idx of final_j * prod_{j<m<idx} decay_m.

    Implemented as an **exclusive prefix scan over the seq axis** via
    log2(S) distance-doubling ``ppermute`` rounds (Hillis-Steele on the
    associative pair combine (a, s) o (a', s') = (a a', s a' + s')),
    followed by a single shift-by-one.  Per round each rank moves one
    O(state) summary over ICI — total O(log S) latency and O(log S *
    state) traffic, vs the O(S * state) every-rank footprint of an
    all-gather formulation; nothing of size S is ever resident.
    ``ppermute`` delivers zeros to ranks with no sender, which is the
    combine's identity for ``s`` but not for ``a`` — those lanes are
    patched to the identity (a=1) by rank index.  ``decay_total`` must be
    broadcastable over ``final_local``.  Shared by the SSD and
    selective-scan SP paths.
    """
    n = ctx.size
    if n == 1:
        return jnp.zeros_like(final_local)
    axis = ctx.axis
    idx = jax.lax.axis_index(axis)

    a = decay_total
    s = final_local
    bcast = lambda v: v.reshape(v.shape + (1,) * (s.ndim - v.ndim))

    d = 1
    while d < n:
        perm = [(i, i + d) for i in range(n - d)]
        a_in = jax.lax.ppermute(a, axis, perm)
        s_in = jax.lax.ppermute(s, axis, perm)
        a_in = jnp.where(idx >= d, a_in, jnp.ones_like(a_in))
        # left-prefix (received) combined into the local value
        s = s_in * bcast(a) + s
        a = a_in * a
        d *= 2

    # inclusive -> exclusive: state entering rank r = prefix through r-1
    return jax.lax.ppermute(s, axis, [(i, i + 1) for i in range(n - 1)])


def sp_selective_scan(
    ctx: SeqContext,
    u: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    B: jax.Array,
    C: jax.Array,
    D: jax.Array | None = None,
    z: jax.Array | None = None,
    delta_bias: jax.Array | None = None,
    delta_softplus: bool = False,
    ssm_impl: str = "xla",
):
    """Sequence-sharded Mamba-1 selective scan.

    Shapes as ops/scan.selective_scan: u/dt/z (b, t, d), A (d, n),
    B/C (b, t, n), with t sharded over ``ctx.axis``.  Two local passes:
    the first produces this shard's (elementwise decay, final state)
    summary, the summaries are all-gathered (O(d*n) traffic, not O(T)),
    and the second pass re-runs the local scan seeded with the combined
    incoming state.  Exact: matches the full-sequence scan to fp32
    tolerance (tests/test_seq_parallel.py).

    The second pass deliberately re-runs the recurrence instead of
    correcting pass 1's output with C_t . (exp(cumsum dt*A) * h_in) —
    that correction needs the (b, t, d, n) cumulative-decay tensor the
    chunked scan exists to avoid materializing, and the M1 recurrence is
    a few percent of layer FLOPs (the projections dominate), so 2x scan
    cost buys O(T/devices) memory with a negligible step-time impact.

    ``ssm_impl="pallas"`` runs both local passes through the fused VMEM
    kernel (ops/pallas/scan_kernels.py — its seeded custom_vjp makes the
    h_in-dependent second pass differentiable); the cross-shard exchange
    stays shard_map/ppermute either way.

    Returns (y, None) — the final state stays on the last shard.
    """
    from mamba_distributed_tpu.ops.scan import _prep, selective_scan

    if ssm_impl == "pallas":
        from mamba_distributed_tpu.ops.pallas import selective_scan_pallas
        scan_fn = selective_scan_pallas
    else:
        scan_fn = selective_scan

    bat3 = P(ctx.batch_axes, ctx.axis, None)
    has_D, has_z, has_bias = D is not None, z is not None, delta_bias is not None

    def local(u_l, dt_l, A_, B_l, C_l, *rest):
        it = iter(rest)
        D_ = next(it) if has_D else None
        z_l = next(it) if has_z else None
        bias_ = next(it) if has_bias else None

        # pass 1: local summary (zero incoming state)
        _, s_local = scan_fn(
            u_l, dt_l, A_, B_l, C_l,
            delta_bias=bias_, delta_softplus=delta_softplus,
            return_final_state=True,
        )
        _, df, Af, _, _, _ = _prep(
            u_l, dt_l, A_, B_l, C_l, None, bias_, delta_softplus
        )
        # elementwise decay over the local shard: exp(sum_t dt_t * A) (b, d, n)
        decay_total = jnp.exp(jnp.einsum("btd,dn->bdn", df, Af))
        h_in = _incoming_state(ctx, decay_total, s_local)

        # pass 2: the real scan, seeded
        return scan_fn(
            u_l, dt_l, A_, B_l, C_l, D=D_, z=z_l,
            delta_bias=bias_, delta_softplus=delta_softplus,
            initial_state=h_in,
        )

    in_specs = [bat3, bat3, P(None, None), bat3, bat3]
    args = [u, dt, A, B, C]
    if has_D:
        in_specs.append(P(None))
        args.append(D)
    if has_z:
        in_specs.append(bat3)
        args.append(z)
    if has_bias:
        in_specs.append(P(None))
        args.append(delta_bias)
    fn = jax.shard_map(
        local, mesh=ctx.mesh, in_specs=tuple(in_specs), out_specs=bat3,
        check_vma=False,
    )
    return fn(*args), None
