"""Pallas selective-scan (Mamba-1) kernel.

TPU-native counterpart of the reference dependency's CUDA selective scan
(``mamba_ssm/csrc/selective_scan/`` in mamba-ssm 2.2.2) — re-derived for
the VPU/VMEM model rather than translated:

  * grid = (batch, d-blocks, t-tiles); the recurrent state lives in a VMEM
    scratch laid out ``(n, d_blk)`` (a (16, 128)-lane vreg tile is exactly
    one state update's working set) and is carried across the sequential
    t-tile dimension, so arbitrarily long sequences stream through a
    bounded VMEM budget;
  * the time loop is sequential *inside* the kernel (the recurrence is
    sequential; the CUDA kernel does the same) — HBM traffic is just
    u/delta in, y out: nothing of shape (b, t, d, n) ever exists, unlike
    the XLA associative-scan path whose per-chunk intermediates are remat
    tricks around exactly that tensor;
  * batch and d-block grid dimensions are marked parallel (megacore);
    state math is fp32 like the CUDA kernel.

Training uses ``jax.custom_vjp`` with a **Pallas backward** (counterpart
of the reference dep's fused CUDA backward in
``mamba_ssm/csrc/selective_scan/selective_scan_bwd_*.cu``): a first
kernel re-runs the forward storing only per-tile entry states, then a
reverse-time kernel walks the t-tiles backwards, rebuilds the in-tile
states in a VMEM scratch from the tile's entry state (the same
recompute-per-chunk trade the CUDA kernel makes with shared memory),
and accumulates du/ddt/dA/dB/dC as it sweeps.  Gradient parity vs the
XLA associative-scan path is pinned by tests/test_pallas.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mamba_distributed_tpu.ops.pallas.common import resolve_interpret
from mamba_distributed_tpu.ops.scan import _prep


_OUTER = (((0,), (0,)), ((), ()))    # (1, n) x (1, d) -> (n, d)
_MATVEC = (((1,), (0,)), ((), ()))   # (1, n) x (n, d) -> (1, d)
_LANES = (((1,), (1,)), ((), ()))    # (1, d) x (n, d) -> (1, n)


def _m1_step(h, At, dt_t, u_t, B_row):
    """One recurrence step: h' = h * exp(A dt) + outer(B, dt u).

    ``B_row`` is (1, n); the outer product runs as a singleton-contracted
    dot_general — Mosaic supports no (1, n) -> (n, 1) shape cast, so
    row-vector B/C never get transposed in-kernel (hardware lesson, r4).
    """
    return h * jnp.exp(At * dt_t) + jax.lax.dot_general(
        B_row, dt_t * u_t, _OUTER, preferred_element_type=jnp.float32,
    )


def _m1_scan_kernel(
    u_ref, dt_ref, At_ref, B_ref, C_ref, h0_ref, y_ref, hT_ref, h_scratch,
    *, nt: int
):
    """Sequential selective scan for one (batch, d-block, t-tile) cell.

    u/dt (1, tb, dblk) fp32; At (n, dblk); B/C (1, tb, n); h0 (1, n, dblk).
    The state is carried across t-tiles in ``h_scratch`` (n, dblk); the
    final tile writes it to hT (1, n, dblk).
    """
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _():
        h_scratch[...] = h0_ref[0]

    At = At_ref[...]          # (n, dblk)
    tb = u_ref.shape[1]

    def body(i, h):
        dt_t = dt_ref[0, pl.ds(i, 1)]              # (1, dblk)
        u_t = u_ref[0, pl.ds(i, 1)]                # (1, dblk)
        B_row = B_ref[0, pl.ds(i, 1)]              # (1, n)
        C_row = C_ref[0, pl.ds(i, 1)]              # (1, n)
        h = _m1_step(h, At, dt_t, u_t, B_row)
        y_ref[0, pl.ds(i, 1)] = jax.lax.dot_general(
            C_row, h, _MATVEC, preferred_element_type=jnp.float32,
        )
        return h

    h_scratch[...] = jax.lax.fori_loop(0, tb, body, h_scratch[...])

    @pl.when(ti == nt - 1)
    def _():
        hT_ref[0] = h_scratch[...]


def _divisor_up_to(x: int, target: int) -> int:
    """Largest divisor of x that is <= target."""
    blk = min(x, target)
    while x % blk != 0:
        blk -= 1
    return blk


def _pick_blocks(t: int, d: int) -> tuple[int, int]:
    """(t_blk, dblk) dividing (t, d), sized for a few-MB VMEM footprint.

    dblk targets 512 lanes (a multiple of the 128-lane vreg width when d
    allows); t_blk then caps the u/dt/y tiles at ~2 MB each in fp32.
    """
    for cand in (512, 256, 128):
        if d % cand == 0:
            dblk = cand
            break
    else:
        dblk = _divisor_up_to(d, 512)
    t_target = max(1, (2 << 20) // (4 * dblk))  # ~2 MB fp32 per (tb, dblk) tile
    t_blk = _divisor_up_to(t, min(t, t_target))
    return t_blk, dblk


def _m1_pallas_fwd(uf, df, Af, Bf, Cf, h0, interpret):
    """fp32 core: (b,t,d)x2, (d,n), (b,t,n)x2, (b,d,n) -> y, final_state."""
    b, t, d = uf.shape
    n = Af.shape[-1]
    t_blk, dblk = _pick_blocks(t, d)
    nt = t // t_blk
    grid = (b, d // dblk, nt)

    io_spec = pl.BlockSpec((1, t_blk, dblk), lambda bi, di, ti: (bi, ti, di))
    bc_spec = pl.BlockSpec((1, t_blk, n), lambda bi, di, ti: (bi, ti, 0))
    st_spec = pl.BlockSpec((1, n, dblk), lambda bi, di, ti: (bi, 0, di))

    y, hT = pl.pallas_call(
        functools.partial(_m1_scan_kernel, nt=nt),
        grid=grid,
        in_specs=[
            io_spec,
            io_spec,
            pl.BlockSpec((n, dblk), lambda bi, di, ti: (0, di)),
            bc_spec,
            bc_spec,
            st_spec,
        ],
        out_specs=[io_spec, st_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, d), jnp.float32),
            jax.ShapeDtypeStruct((b, n, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, dblk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(uf, df, Af.T, Bf, Cf, jnp.swapaxes(h0, 1, 2))
    return y, jnp.swapaxes(hT, 1, 2)


# ---------------------------------------------------------------------------
# Backward pass.  Recurrence (per batch, channel, state lane n):
#     h_i = h_{i-1} * e_i + dt_i u_i B_i,   e_i = exp(A dt_i)
#     y_i = <h_i, C_i>
# Reverse sweep with gh = dL/dh_i accumulated right-to-left:
#     gh   += C_i (x) dy_i
#     dC_i  = sum_d h_i dy_i            dB_i = sum_d gh dt_i u_i
#     ddt_i = sum_n gh (h_{i-1} A e_i + u_i B_i)
#     du_i  = dt_i sum_n gh B_i         dA  += gh e_i h_{i-1} dt_i
#     gh   *= e_i
# h_{i-1} is rebuilt per tile from a stored tile-entry state, so the
# backward's HBM footprint stays O(t/t_blk) states, not O(t).
# ---------------------------------------------------------------------------


def _m1_entry_states_kernel(
    u_ref, dt_ref, At_ref, B_ref, h0_ref, st_ref, h_scratch
):
    """Forward recompute writing each t-tile's *entry* state."""
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _():
        h_scratch[...] = h0_ref[0]

    st_ref[0, 0] = h_scratch[...]
    At = At_ref[...]
    tb = u_ref.shape[1]

    def body(i, h):
        dt_t = dt_ref[0, pl.ds(i, 1)]
        u_t = u_ref[0, pl.ds(i, 1)]
        B_row = B_ref[0, pl.ds(i, 1)]              # (1, n)
        return _m1_step(h, At, dt_t, u_t, B_row)

    h_scratch[...] = jax.lax.fori_loop(0, tb, body, h_scratch[...])


def _m1_bwd_kernel(
    u_ref, dt_ref, At_ref, B_ref, C_ref, hin_ref, dy_ref, dfinal_ref,
    du_ref, ddt_ref, dA_ref, dB_ref, dC_ref, dh0_ref,
    gh_scratch, hbuf, dA_scratch, *, nt: int,
):
    """Reverse sweep over one (batch, d-block, reversed t-tile) cell.

    hbuf[i] holds h_{i-1} (the state *entering* step i), rebuilt from the
    tile's entry state; gh and the dA accumulator persist across the
    sequential (reversed) tile dimension in scratch.  ``dfinal`` (the
    final-state cotangent — zeros for an unseeded call) seeds gh at the
    reverse start; after the full sweep gh IS the initial-state gradient,
    emitted as ``dh0``.
    """
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _():
        gh_scratch[...] = dfinal_ref[0]
        dA_scratch[...] = jnp.zeros_like(dA_scratch)

    At = At_ref[...]          # (n, dblk)
    tb = u_ref.shape[1]

    # forward in-tile recompute: hbuf[i] = state before step i
    def fwd_body(i, h):
        hbuf[pl.ds(i, 1)] = h[None]
        dt_t = dt_ref[0, pl.ds(i, 1)]
        u_t = u_ref[0, pl.ds(i, 1)]
        B_row = B_ref[0, pl.ds(i, 1)]
        return _m1_step(h, At, dt_t, u_t, B_row)

    jax.lax.fori_loop(0, tb, fwd_body, hin_ref[0, 0])

    ones_n = jnp.ones((1, At.shape[0]), jnp.float32)

    # reverse sweep (row-vector forms throughout: outer products and
    # sublane contractions via dot_general, never a (1, n) -> (n, 1) cast)
    def rev_body(k, carry):
        gh, dA = carry
        i = tb - 1 - k
        dt_t = dt_ref[0, pl.ds(i, 1)]              # (1, dblk)
        u_t = u_ref[0, pl.ds(i, 1)]
        dy_t = dy_ref[0, pl.ds(i, 1)]
        B_row = B_ref[0, pl.ds(i, 1)]              # (1, n)
        C_row = C_ref[0, pl.ds(i, 1)]
        hprev = hbuf[i]                            # (n, dblk)

        e_t = jnp.exp(At * dt_t)
        gh = gh + jax.lax.dot_general(             # += outer(C, dy)
            C_row, dy_t, _OUTER, preferred_element_type=jnp.float32,
        )
        hcur = _m1_step(hprev, At, dt_t, u_t, B_row)
        dC_ref[0, 0, pl.ds(i, 1)] = jax.lax.dot_general(
            dy_t, hcur, _LANES, preferred_element_type=jnp.float32,
        )                                          # (1, n)
        dB_ref[0, 0, pl.ds(i, 1)] = jax.lax.dot_general(
            dt_t * u_t, gh, _LANES, preferred_element_type=jnp.float32,
        )
        term = hprev * At * e_t + jax.lax.dot_general(
            B_row, u_t, _OUTER, preferred_element_type=jnp.float32,
        )
        ddt_ref[0, pl.ds(i, 1)] = jax.lax.dot_general(
            ones_n, gh * term, _MATVEC, preferred_element_type=jnp.float32,
        )                                          # (1, dblk) sublane sum
        du_ref[0, pl.ds(i, 1)] = dt_t * jax.lax.dot_general(
            B_row, gh, _MATVEC, preferred_element_type=jnp.float32,
        )
        ghe = gh * e_t
        dA = dA + ghe * hprev * dt_t
        return ghe, dA

    gh, dA = jax.lax.fori_loop(
        0, tb, rev_body, (gh_scratch[...], dA_scratch[...])
    )
    gh_scratch[...] = gh
    dA_scratch[...] = dA

    @pl.when(ti == nt - 1)
    def _():
        dA_ref[0] = dA_scratch[...]
        # gh after the earliest step == dL/d(initial state)
        dh0_ref[0] = gh_scratch[...]


def _m1_pallas_bwd_impl(uf, df, Af, Bf, Cf, dy, interpret,
                        h0=None, dfinal=None):
    """Entry-state recompute + reverse kernel + tiny XLA reductions.

    ``h0``/``dfinal`` are (b, d, n) seeded-call extras: the entry-state
    recompute starts from h0, dfinal seeds the reverse sweep, and the
    initial-state gradient comes back as the sixth output (b, d, n).
    """
    b, t, d = uf.shape
    n = Af.shape[-1]
    t_blk, dblk = _pick_blocks(t, d)
    # the reverse kernel keeps (t_blk, n, dblk) rebuilt states in VMEM;
    # shrink the tile if that buffer would exceed ~4 MB
    cap = max(1, (4 << 20) // (4 * n * dblk))
    if t_blk > cap:
        t_blk = _divisor_up_to(t, cap)
    nt = t // t_blk
    nd = d // dblk
    grid = (b, nd, nt)
    At = Af.T
    h0 = (
        jnp.zeros((b, n, d), jnp.float32)
        if h0 is None
        else jnp.swapaxes(h0, 1, 2).astype(jnp.float32)
    )
    dfinal = (
        jnp.zeros((b, n, d), jnp.float32)
        if dfinal is None
        else jnp.swapaxes(dfinal, 1, 2).astype(jnp.float32)
    )

    io_spec = pl.BlockSpec((1, t_blk, dblk), lambda bi, di, ti: (bi, ti, di))
    bc_spec = pl.BlockSpec((1, t_blk, n), lambda bi, di, ti: (bi, ti, 0))
    A_spec = pl.BlockSpec((n, dblk), lambda bi, di, ti: (0, di))
    seq_semantics = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
    )

    entry_states = pl.pallas_call(
        _m1_entry_states_kernel,
        grid=grid,
        in_specs=[
            io_spec, io_spec, A_spec, bc_spec,
            pl.BlockSpec((1, n, dblk), lambda bi, di, ti: (bi, 0, di)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, n, dblk), lambda bi, di, ti: (bi, ti, 0, di)
        ),
        out_shape=jax.ShapeDtypeStruct((b, nt, n, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, dblk), jnp.float32)],
        compiler_params=seq_semantics,
        interpret=interpret,
    )(uf, df, At, Bf, h0)

    # reversed sequential tile order via the index maps
    rio_spec = pl.BlockSpec(
        (1, t_blk, dblk), lambda bi, di, ti: (bi, nt - 1 - ti, di)
    )
    rbc_spec = pl.BlockSpec(
        (1, t_blk, n), lambda bi, di, ti: (bi, nt - 1 - ti, 0)
    )
    st_spec = pl.BlockSpec((1, n, dblk), lambda bi, di, ti: (bi, 0, di))
    du, ddt, dA_part, dB_part, dC_part, dh0 = pl.pallas_call(
        functools.partial(_m1_bwd_kernel, nt=nt),
        grid=grid,
        in_specs=[
            rio_spec, rio_spec, A_spec, rbc_spec, rbc_spec,
            pl.BlockSpec((1, 1, n, dblk), lambda bi, di, ti: (bi, nt - 1 - ti, 0, di)),
            rio_spec,
            st_spec,
        ],
        out_specs=[
            rio_spec,
            rio_spec,
            pl.BlockSpec((1, n, dblk), lambda bi, di, ti: (bi, 0, di)),
            pl.BlockSpec((1, 1, t_blk, n), lambda bi, di, ti: (bi, di, nt - 1 - ti, 0)),
            pl.BlockSpec((1, 1, t_blk, n), lambda bi, di, ti: (bi, di, nt - 1 - ti, 0)),
            st_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, d), jnp.float32),
            jax.ShapeDtypeStruct((b, t, d), jnp.float32),
            jax.ShapeDtypeStruct((b, n, d), jnp.float32),
            jax.ShapeDtypeStruct((b, nd, t, n), jnp.float32),
            jax.ShapeDtypeStruct((b, nd, t, n), jnp.float32),
            jax.ShapeDtypeStruct((b, n, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n, dblk), jnp.float32),
            pltpu.VMEM((t_blk, n, dblk), jnp.float32),
            pltpu.VMEM((n, dblk), jnp.float32),
        ],
        compiler_params=seq_semantics,
        interpret=interpret,
    )(uf, df, At, Bf, Cf, entry_states, dy, dfinal)

    dAf = jnp.sum(dA_part, axis=0).T           # (d, n)
    dBf = jnp.sum(dB_part, axis=1)             # (b, t, n)
    dCf = jnp.sum(dC_part, axis=1)
    return du, ddt, dAf, dBf, dCf, jnp.swapaxes(dh0, 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _m1_core(uf, df, Af, Bf, Cf, h0, interpret, return_final_state):
    y, hT = _m1_pallas_fwd(uf, df, Af, Bf, Cf, h0, interpret)
    return (y, hT) if return_final_state else y


def _m1_core_fwd(uf, df, Af, Bf, Cf, h0, interpret, return_final_state):
    out = _m1_core(uf, df, Af, Bf, Cf, h0, interpret, return_final_state)
    return out, (uf, df, Af, Bf, Cf, h0)


def _m1_core_bwd(interpret, return_final_state, res, ct):
    """Pallas backward (see the backward section above)."""
    uf, df, Af, Bf, Cf, h0 = res
    dy, dfinal = ct if return_final_state else (ct, None)
    du, ddt, dAf, dBf, dCf, dh0 = _m1_pallas_bwd_impl(
        uf, df, Af, Bf, Cf, dy.astype(jnp.float32), interpret,
        h0=h0, dfinal=dfinal,
    )
    return du, ddt, dAf, dBf, dCf, dh0


_m1_core.defvjp(_m1_core_fwd, _m1_core_bwd)


def selective_scan_pallas(
    u: jax.Array,
    delta: jax.Array,
    A: jax.Array,
    B: jax.Array,
    C: jax.Array,
    D: jax.Array | None = None,
    z: jax.Array | None = None,
    delta_bias: jax.Array | None = None,
    delta_softplus: bool = False,
    initial_state: jax.Array | None = None,
    return_final_state: bool = False,
    interpret: bool | None = None,
):
    """Drop-in for ops/scan.selective_scan backed by the Pallas kernel.

    Every path — plain training, seeded (``initial_state``: decode
    prefill / SP shards), and ``return_final_state`` — runs under the
    custom VJP whose backward is itself Pallas: the entry-state
    recompute starts from the same seed, a final-state cotangent seeds
    the reverse sweep, and the initial-state gradient is returned.
    ``interpret=None`` auto-selects the Pallas interpreter off-TPU (CPU
    tests run the same kernel code).

    The channel axis is padded to a multiple of the 128-lane vreg width
    and t to a multiple of 8 sublanes, so Mosaic only ever sees aligned
    BlockSpecs; the padding is numerically inert (u=dt=A=0 channels/steps
    carry zero state and are sliced off), autodiff handles the pad/slice,
    and interpret mode takes the identical path so CPU tests exercise it.
    """
    interpret = resolve_interpret(interpret)

    b, t, d = u.shape
    uf, df, Af, Bf, Cf, Df = _prep(u, delta, A, B, C, D, delta_bias, delta_softplus)

    pad_d = -d % 128
    pad_t = -t % 8
    if pad_d or pad_t:
        pt, pd = (0, pad_t), (0, pad_d)
        uf = jnp.pad(uf, ((0, 0), pt, pd))
        df = jnp.pad(df, ((0, 0), pt, pd))
        Af = jnp.pad(Af, (pd, (0, 0)))
        Bf = jnp.pad(Bf, ((0, 0), pt, (0, 0)))
        Cf = jnp.pad(Cf, ((0, 0), pt, (0, 0)))

    h0 = (
        jnp.zeros((b, d + pad_d, Af.shape[-1]), jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32)
    )
    if pad_d and initial_state is not None:
        h0 = jnp.pad(h0, ((0, 0), (0, pad_d), (0, 0)))
    out = _m1_core(uf, df, Af, Bf, Cf, h0, interpret, return_final_state)
    if return_final_state:
        y, h_last = out
        if pad_d:
            h_last = h_last[:, :d]
    else:
        y, h_last = out, None

    if pad_d or pad_t:
        y = y[:, :t, :d]
        uf = uf[:, :t, :d]

    if Df is not None:
        y = y + uf * Df[None, None, :]
    if z is not None:
        y = y * jax.nn.silu(z.astype(jnp.float32))
    y = y.astype(u.dtype)
    if return_final_state:
        return y, h_last
    return y
