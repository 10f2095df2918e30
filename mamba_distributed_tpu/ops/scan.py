"""Selective scan (Mamba-1 SSM recurrence), TPU-native.

Equivalent of the reference dependency's CUDA selective scan
(``mamba_ssm/csrc/selective_scan/`` + ``mamba_ssm/ops/selective_scan_interface.py``
in mamba-ssm 2.2.2, pinned at reference requirements.txt:2) — the kernel the
reference's default ``MambaConfig`` actually executes (SURVEY.md section 2.4).

Recurrence (per batch, channel d, state n):
    h_t = exp(dt_t * A) * h_{t-1} + dt_t * u_t * B_t
    y_t = <C_t, h_t> + D * u_t           (then y *= silu(z) if gated)

Two implementations:
  * ``selective_scan_seq`` — sequential ``lax.scan`` over time; the oracle.
  * ``selective_scan`` — chunked: within a chunk a work-efficient
    ``associative_scan``, between chunks a ``lax.scan`` carry.  The chunk
    body is rematerialized so the backward pass does not store the
    (b, l, d, n) scan intermediates for the whole sequence — this is what
    makes the d_state=16 recurrence fit HBM at T=1024 x 64 layers.

All state math runs in fp32 regardless of input dtype (the CUDA kernel does
the same); inputs/outputs keep the caller's dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _divisor_chunk(t: int, chunk_size: int) -> int:
    """Largest chunk size <= chunk_size that divides t (t is a static shape).

    Warns when the divisor degrades badly (e.g. prime-ish t forces tiny
    chunks): the chunked scans then degenerate toward per-token work.  t is
    static under jit, so the warning fires at trace time, once per shape.
    """
    l = min(chunk_size, t)
    while t % l != 0:
        l -= 1
    if 4 * l <= min(chunk_size, t):
        import warnings

        warnings.warn(
            f"sequence length {t} has no divisor near chunk_size={chunk_size}; "
            f"falling back to chunk size {l}, which degrades the chunked scan "
            f"toward per-token work — pad the sequence to a multiple of a "
            f"reasonable chunk size instead",
            stacklevel=3,
        )
    return l


def _prep(u, delta, A, B, C, D, delta_bias, delta_softplus):
    """Common fp32 promotion + delta preprocessing."""
    uf = u.astype(jnp.float32)
    df = delta.astype(jnp.float32)
    if delta_bias is not None:
        df = df + delta_bias.astype(jnp.float32)
    if delta_softplus:
        df = jax.nn.softplus(df)
    Af = A.astype(jnp.float32)
    Bf = B.astype(jnp.float32)
    Cf = C.astype(jnp.float32)
    Df = None if D is None else D.astype(jnp.float32)
    return uf, df, Af, Bf, Cf, Df


def selective_scan_seq(
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
):
    """Oracle: plain sequential scan over time.

    Shapes: u/delta (b, t, d); A (d, n); B/C (b, t, n); D (d,); z (b, t, d);
    initial_state (b, d, n).
    """
    b, t, d = u.shape
    n = A.shape[-1]
    uf, df, Af, Bf, Cf, Df = _prep(u, delta, A, B, C, D, delta_bias, delta_softplus)

    h0 = (
        jnp.zeros((b, d, n), jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32)
    )

    def step(h, inputs):
        u_t, dt_t, B_t, C_t = inputs  # (b,d) (b,d) (b,n) (b,n)
        dA = jnp.exp(dt_t[:, :, None] * Af[None])  # (b, d, n)
        dBu = (dt_t * u_t)[:, :, None] * B_t[:, None, :]  # (b, d, n)
        h = h * dA + dBu
        y_t = jnp.einsum("bdn,bn->bd", h, C_t)
        return h, y_t

    xs = (
        jnp.moveaxis(uf, 1, 0),
        jnp.moveaxis(df, 1, 0),
        jnp.moveaxis(Bf, 1, 0),
        jnp.moveaxis(Cf, 1, 0),
    )
    h_last, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1)  # (b, t, d)
    if Df is not None:
        y = y + uf * Df[None, None, :]
    if z is not None:
        y = y * jax.nn.silu(z.astype(jnp.float32))
    y = y.astype(u.dtype)
    if return_final_state:
        return y, h_last
    return y


def _chunk_scan(h0, u_i, dt_i, Af, B_i, C_i):
    """One chunk: associative scan over the local time axis.

    The (b, l, d, n) intermediates are built *inside* this function so that,
    wrapped in ``jax.checkpoint``, they exist only transiently per chunk in
    both forward and backward.

    h0 (b, d, n); u_i/dt_i (b, l, d); Af (d, n); B_i/C_i (b, l, n).
    Returns (y (b, l, d), h_last (b, d, n)).
    """
    dA = jnp.exp(dt_i[..., None] * Af[None, None])  # (b, l, d, n)
    dBu = (dt_i * u_i)[..., None] * B_i[:, :, None, :]  # (b, l, d, n)

    def combine(left, right):
        a_l, b_l = left
        a_r, b_r = right
        return a_l * a_r, b_l * a_r + b_r

    # fold the carried state into the first element
    dBu = dBu.at[:, 0].add(h0 * dA[:, 0])
    _, h = jax.lax.associative_scan(combine, (dA, dBu), axis=1)
    y = jnp.einsum("bldn,bln->bld", h, C_i)
    return y, h[:, -1]


def selective_scan(
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
    chunk_size: int = 128,
):
    """Production path: chunked associative scan with rematerialization."""
    b, t, d = u.shape
    n = A.shape[-1]
    uf, df, Af, Bf, Cf, Df = _prep(u, delta, A, B, C, D, delta_bias, delta_softplus)

    h0 = (
        jnp.zeros((b, d, n), jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32)
    )

    l = _divisor_chunk(t, chunk_size)
    nc = t // l

    chunk_body = jax.checkpoint(_chunk_scan)

    def outer(h, inputs):
        u_i, dt_i, B_i, C_i = inputs
        y_i, h = chunk_body(h, u_i, dt_i, Af, B_i, C_i)
        return h, y_i

    xs = (
        jnp.moveaxis(uf.reshape(b, nc, l, d), 1, 0),
        jnp.moveaxis(df.reshape(b, nc, l, d), 1, 0),
        jnp.moveaxis(Bf.reshape(b, nc, l, n), 1, 0),
        jnp.moveaxis(Cf.reshape(b, nc, l, n), 1, 0),
    )
    h_last, ys = jax.lax.scan(outer, h0, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(b, t, d)

    if Df is not None:
        y = y + uf * Df[None, None, :]
    if z is not None:
        y = y * jax.nn.silu(z.astype(jnp.float32))
    y = y.astype(u.dtype)
    if return_final_state:
        return y, h_last
    return y


def selective_state_update(
    ssm_state: jax.Array,
    x_t: jax.Array,
    dt_t: jax.Array,
    A: jax.Array,
    B_t: jax.Array,
    C_t: jax.Array,
    D: jax.Array | None = None,
    z_t: jax.Array | None = None,
    dt_bias: jax.Array | None = None,
    dt_softplus: bool = True,
    state_mask: jax.Array | None = None,
):
    """O(1)-per-token recurrent step for decode (Mamba-1 shapes).

    Equivalent of ``mamba_ssm/ops/triton/selective_state_update.py``.

    ssm_state (b, d, n); x_t/dt_t (b, d); A (d, n); B_t/C_t (b, n).
    Returns (y_t (b, d), new_state).  ``state_mask`` (b,) bool: rows where
    it is False get their state back unchanged (``None`` advances every
    row), as in ``ops/ssd.ssd_state_update``.  The other rows' values are
    the unmasked call's op for op; compiled, the CPU backend contracts one
    of this update's two elementwise products into the add and the select
    can change which, so there they may differ in the last bit
    (tests/test_prefill.py::test_lm_step_state_mask_holds_rows).
    """
    hf = ssm_state.astype(jnp.float32)
    xf = x_t.astype(jnp.float32)
    dtf = dt_t.astype(jnp.float32)
    if dt_bias is not None:
        dtf = dtf + dt_bias.astype(jnp.float32)
    if dt_softplus:
        dtf = jax.nn.softplus(dtf)
    dA = jnp.exp(dtf[:, :, None] * A.astype(jnp.float32)[None])
    dBu = (dtf * xf)[:, :, None] * B_t.astype(jnp.float32)[:, None, :]
    h = hf * dA + dBu
    if state_mask is not None:
        h = jnp.where(state_mask[:, None, None], h, hf)
    y = jnp.einsum("bdn,bn->bd", h, C_t.astype(jnp.float32))
    if D is not None:
        y = y + xf * D.astype(jnp.float32)[None]
    if z_t is not None:
        y = y * jax.nn.silu(z_t.astype(jnp.float32))
    return y.astype(x_t.dtype), h
