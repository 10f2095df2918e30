"""SSD chunked scan (Mamba-2 "state-space duality"), TPU-native.

Equivalent of the reference dependency's Triton SSD kernels
(``mamba_ssm/ops/triton/ssd_combined.py``, ``ssd_chunk_scan.py``,
``ssd_chunk_state.py``, ``ssd_state_passing.py``, ``ssd_bmm.py`` in
mamba-ssm 2.2.2, pinned at reference requirements.txt:2).

The algorithm is re-derived for the MXU rather than translated: the sequence
is split into chunks of length L; within a chunk the recurrence is expressed
as batched (L x N) @ (N x L) and (L x L) @ (L x P) matmuls (pure MXU work),
while the tiny per-chunk states (H, P, N) flow through an associative scan
over chunks.  The same per-chunk state decomposition is what sequence
parallelism rides on (each device computes its local chunk states; only the
(H, P, N) boundary states cross devices — see parallel/seq_parallel.py and
SURVEY.md section 5).

Recurrence (per batch, head h, state n, head-channel p):
    h_t = exp(dt_t * A_h) * h_{t-1} + dt_t * B_t  x_t^T        (outer product)
    y_t = C_t . h_t + D_h * x_t

Shapes (group g broadcasts over the heads it owns, heads-per-group = H/G):
    x  (b, t, h, p)      dt (b, t, h)   [already bias-added + softplus-ed]
    A  (h,) negative     B, C (b, t, g, n)
    D  (h,) or (h, p)    initial_state (b, h, p, n)

Decay math runs in fp32 (differences of cumulative log-decays stay <= 0, so
exp() never overflows); the big matmuls run in the compute dtype with fp32
accumulation (``preferred_element_type``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from mamba_distributed_tpu.obs import scopes


def cumsum_mxu(x: jax.Array, axis: int = -1, reverse: bool = False) -> jax.Array:
    """Inclusive (reverse-)cumsum as a triangular matmul.

    ``jnp.cumsum`` lowers to a sequential reduce-window on TPU (measured
    ~25 ms/step across the 280M model's four cumsum sites, round-4 trace);
    a (l, l) lower-triangular ones matmul computes the same prefix sums on
    the MXU at negligible cost and fuses with the surrounding decay math.
    The transposed triangle gives the reverse cumsum, so the custom-vjp-free
    gradient (a reverse cumsum) rides the MXU too.
    """
    l = x.shape[axis]
    tri = jnp.tril(jnp.ones((l, l), jnp.float32))
    if reverse:
        tri = tri.T
    xm = jnp.moveaxis(x, axis, -1)
    out = jnp.einsum(
        "...s,ls->...l", xm.astype(jnp.float32), tri,
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)
    return jnp.moveaxis(out, -1, axis)


def segsum(x: jax.Array) -> jax.Array:
    """Segment-sum: out[..., i, j] = sum_{k in (j, i]} x[..., k] for i >= j.

    Returns -inf above the diagonal so that exp(segsum) is the lower-
    triangular decay matrix with ones on the diagonal.
    """
    l = x.shape[-1]
    cs = cumsum_mxu(x, axis=-1)
    d = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((l, l), dtype=bool))
    return jnp.where(mask, d, -jnp.inf)


def _expand_groups(BC: jax.Array, nheads: int) -> jax.Array:
    """(b, t, g, n) -> (b, t, h, n) by repeating each group over its heads."""
    g = BC.shape[2]
    if g == nheads:
        return BC
    assert nheads % g == 0
    return jnp.repeat(BC, nheads // g, axis=2)


def ssd_seq(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    B: jax.Array,
    C: jax.Array,
    D: jax.Array | None = None,
    initial_state: jax.Array | None = None,
    return_final_state: bool = False,
):
    """Oracle: sequential scan over time (fp32 throughout)."""
    b, t, h, p = x.shape
    n = B.shape[-1]
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    Bf = _expand_groups(B, h).astype(jnp.float32)
    Cf = _expand_groups(C, h).astype(jnp.float32)

    s0 = (
        jnp.zeros((b, h, p, n), jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32)
    )

    def step(s, inputs):
        x_t, dt_t, B_t, C_t = inputs  # (b,h,p) (b,h) (b,h,n) (b,h,n)
        decay = jnp.exp(dt_t * Af[None])  # (b, h)
        s = s * decay[:, :, None, None] + jnp.einsum(
            "bhp,bhn,bh->bhpn", x_t, B_t, dt_t
        )
        y_t = jnp.einsum("bhpn,bhn->bhp", s, C_t)
        return s, y_t

    xs = (
        jnp.moveaxis(xf, 1, 0),
        jnp.moveaxis(dtf, 1, 0),
        jnp.moveaxis(Bf, 1, 0),
        jnp.moveaxis(Cf, 1, 0),
    )
    s_last, ys = jax.lax.scan(step, s0, xs)
    y = jnp.moveaxis(ys, 0, 1)
    if D is not None:
        Df = D.astype(jnp.float32)
        y = y + xf * (Df[None, None, :, :] if Df.ndim == 2 else Df[None, None, :, None])
    y = y.astype(x.dtype)
    if return_final_state:
        return y, s_last
    return y


@jax.named_scope(scopes.CHUNK_LOCAL)
def chunk_local(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    B: jax.Array,
    C: jax.Array,
    chunk_size: int,
    compute_dtype=jnp.bfloat16,
):
    """Per-chunk compute: diagonal-block outputs + chunk summaries.

    This is the device-local portion of SSD — everything except the
    inter-chunk state recurrence.  Sequence parallelism calls this on the
    local shard and runs the state recurrence across devices.

    Returns:
      y_diag       (b, nc, l, h, p) intra-chunk contribution
      states       (b, nc, h, p, n) per-chunk final state contribution
      chunk_decay  (b, nc, h)       exp(sum of dt*A over the chunk)
      off_ctx      (C (b, nc, l, g, n) compute-dtype, state_decay
                   (b, nc, l, h) fp32) — inputs to the off-diagonal
                   correction (combine_chunk_outputs)

    B and C stay in their group-compact (g, n) form throughout: the G
    Gram matrix is computed once per group (h/g-fold fewer MACs than the
    per-head formulation), per-head decay scalars attach to the tensors
    that are already per-head (x, the off-diagonal output), and nothing
    of shape (b, t, h, n) is ever materialized.
    """
    b, t, h, p = x.shape
    g, n = B.shape[2], B.shape[-1]
    assert h % g == 0
    hg = h // g
    l = chunk_size
    assert t % l == 0, (t, l)
    nc = t // l

    dtf = dt.astype(jnp.float32)
    Af = A.astype(jnp.float32)

    xc = x.reshape(b, nc, l, h, p)
    dtc = dtf.reshape(b, nc, l, h)
    Bc = B.reshape(b, nc, l, g, n)
    Cc = C.reshape(b, nc, l, g, n)

    dA = dtc * Af  # (b, nc, l, h), <= 0
    dA_cum = cumsum_mxu(dA, axis=2)  # inclusive cumsum within chunk

    # --- intra-chunk (diagonal blocks): batched MXU matmuls ---
    # G[i, j] = <C_i, B_j> is group-shared -> (b, nc, g, l, l)
    G = jnp.einsum(
        "bclgn,bcsgn->bcgls",
        Cc.astype(compute_dtype),
        Bc.astype(compute_dtype),
        preferred_element_type=jnp.float32,
    )
    # decay math in fp32 (exp of <=0 stays stable), but the (l x l) masked
    # decay matrix — the biggest intermediate of the whole op, O(b*t*h*l) —
    # is materialized in the compute dtype to halve its HBM traffic
    L_mat = jnp.exp(segsum(jnp.moveaxis(dA, 2, -1)))  # (b, nc, h, l, l)
    Lg = L_mat.reshape(b, nc, g, hg, l, l)
    M = (G[:, :, :, None] * Lg).astype(compute_dtype).reshape(b, nc, h, l, l)
    xdt = (xc.astype(jnp.float32) * dtc[..., None]).astype(compute_dtype)
    y_diag = jnp.einsum(
        "bchls,bcshp->bclhp",
        M,
        xdt,
        preferred_element_type=jnp.float32,
    )

    # --- per-chunk state summaries (per-head decay*dt attaches to x) ---
    decay_states = jnp.exp(dA_cum[:, :, -1:, :] - dA_cum)  # (b, nc, l, h)
    xg = (
        (xc.astype(jnp.float32) * (decay_states * dtc)[..., None])
        .astype(compute_dtype)
        .reshape(b, nc, l, g, hg, p)
    )
    states = jnp.einsum(
        "bclgn,bclgjp->bcgjpn",
        Bc.astype(compute_dtype),
        xg,
        preferred_element_type=jnp.float32,
    ).reshape(b, nc, h, p, n)
    chunk_decay = jnp.exp(dA_cum[:, :, -1, :])  # (b, nc, h)
    off_ctx = (Cc.astype(compute_dtype), jnp.exp(dA_cum))
    return y_diag, states, chunk_decay, off_ctx


# Above this chunk count the O(nc^2) decay-weight einsum in state_passing
# yields to the O(log nc) associative scan (tests force the fallback by
# patching this).
_STATE_PASSING_EINSUM_MAX_NC = 256


@jax.named_scope(scopes.STATE_PASSING)
def state_passing(
    states: jax.Array,
    chunk_decay: jax.Array,
    initial_state: jax.Array | None = None,
):
    """Inter-chunk state recurrence via associative scan.

    states (b, nc, h, p, n); chunk_decay (b, nc, h).
    Returns (prev_states (b, nc, h, p, n) — the state *entering* each chunk —
    and final_state (b, h, p, n)).
    """
    b, nc, h, p, n = states.shape
    if nc <= _STATE_PASSING_EINSUM_MAX_NC:
        # Dominant path: the recurrence as one lower-triangular decay-
        # weighted einsum on the MXU.  The associative_scan formulation
        # pads/slices the full (b, nc, h, p, n) array every round (six
        # whole-array pad ops ≈ 44 ms/step on the 280M config, round-4
        # trace); the matmul is O(nc^2) in tiny chunk counts and touches
        # each state tensor exactly once.  Log-space decays keep it exact:
        # cum is non-increasing, so every exp argument is <= 0.  Clamping
        # at fp32-tiny only affects per-chunk decays that already
        # underflowed to zero, where exp(diff) underflows to zero too.
        ldc = jnp.log(
            jnp.maximum(
                chunk_decay.astype(jnp.float32), jnp.finfo(jnp.float32).tiny
            )
        )
        cum = cumsum_mxu(ldc, axis=1)  # (b, nc, h)
        # W[c, j] = prod of decays (j, c] = exp(cum[c] - cum[j]) for j <= c
        diff = cum[:, :, None, :] - cum[:, None, :, :]  # (b, c, j, h)
        tri = jnp.tril(jnp.ones((nc, nc), dtype=bool))[None, :, :, None]
        # double-where: above the diagonal diff >= 0 can overflow exp and
        # the dead branch would still NaN the gradient
        safe = jnp.where(tri, diff, -100.0)
        W = jnp.where(tri, jnp.exp(safe), 0.0).astype(states.dtype)
        s_cum = jnp.einsum(
            "bcjh,bjhpn->bchpn", W, states,
            preferred_element_type=jnp.float32,
        ).astype(states.dtype)
        if initial_state is not None:
            s0 = initial_state.astype(jnp.float32)[:, None]
            a_cum = jnp.exp(cum)[..., None, None].astype(jnp.float32)
            s_cum = (s_cum.astype(jnp.float32) + a_cum * s0).astype(
                states.dtype
            )
    else:
        decay = chunk_decay[..., None, None]  # (b, nc, h, 1, 1)

        def combine(left, right):
            a_l, s_l = left
            a_r, s_r = right
            # a stays (b, nc, h, 1, 1); broadcast only against states
            return a_l * a_r, s_l * a_r + s_r

        a_cum, s_cum = jax.lax.associative_scan(
            combine, (decay, states), axis=1
        )
        # s_cum[c] = state *after* chunk c assuming zero initial state.
        if initial_state is not None:
            s0 = initial_state.astype(states.dtype)[:, None]
            s_cum = s_cum + a_cum * s0
    final_state = s_cum[:, -1]
    # state entering chunk c = s_cum[c-1]; chunk 0 gets the initial state.
    s0_in = (
        jnp.zeros((b, 1, h, p, n), states.dtype)
        if initial_state is None
        else initial_state.astype(states.dtype)[:, None]
    )
    prev_states = jnp.concatenate([s0_in, s_cum[:, :-1]], axis=1)
    return prev_states, final_state


@jax.named_scope(scopes.COMBINE_CHUNK_OUTPUTS)
def combine_chunk_outputs(
    y_diag: jax.Array,
    off_ctx: tuple[jax.Array, jax.Array],
    prev_states: jax.Array,
    x: jax.Array,
    D: jax.Array | None,
    compute_dtype,
) -> jax.Array:
    """Assemble the SSD output from per-chunk pieces.

    Shared by the single-device path (ssd_chunked) and the sequence-
    parallel path (parallel/seq_parallel.sp_ssd): off-diagonal correction
    through the carried states + optional D skip connection.  The per-head
    decay scalar multiplies the einsum *output*, so C never expands past
    its group-compact form.
    """
    b, nc, l, h, p = y_diag.shape
    Cc, state_decay = off_ctx  # (b, nc, l, g, n), (b, nc, l, h)
    g = Cc.shape[3]
    n = prev_states.shape[-1]
    prev_g = prev_states.reshape(b, nc, g, h // g, p, n)
    y_off = jnp.einsum(
        "bclgn,bcgjpn->bclgjp",
        Cc.astype(compute_dtype),
        prev_g.astype(compute_dtype),
        preferred_element_type=jnp.float32,
    ).reshape(b, nc, l, h, p)
    y_off = y_off * state_decay[..., None]
    y = (y_diag + y_off).reshape(b, nc * l, h, p)
    if D is not None:
        Df = D.astype(jnp.float32)
        y = y + x.astype(jnp.float32) * (
            Df[None, None, :, :] if Df.ndim == 2 else Df[None, None, :, None]
        )
    return y.astype(x.dtype)


@jax.named_scope(scopes.SSD)
def ssd_chunked(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    B: jax.Array,
    C: jax.Array,
    chunk_size: int = 256,
    D: jax.Array | None = None,
    initial_state: jax.Array | None = None,
    return_final_state: bool = False,
    compute_dtype=jnp.bfloat16,
):
    """Full chunked SSD forward (single device).

    Wall-to-wall: chunk_local -> state_passing -> off-diagonal correction.
    Autodiff-friendly; the backward pass is XLA-derived from the same matmul
    graph (all matmuls, so it stays on the MXU).
    """
    from mamba_distributed_tpu.ops.scan import _divisor_chunk

    b, t, h, p = x.shape
    l = _divisor_chunk(t, chunk_size)

    y_diag, states, chunk_decay, off_ctx = chunk_local(
        x, dt, A, B, C, l, compute_dtype
    )
    prev_states, final_state = state_passing(states, chunk_decay, initial_state)
    y = combine_chunk_outputs(y_diag, off_ctx, prev_states, x, D, compute_dtype)
    if return_final_state:
        return y, final_state
    return y


@jax.named_scope(scopes.SSD)
def ssd_state_update(
    ssm_state: jax.Array,
    x_t: jax.Array,
    dt_t: jax.Array,
    A: jax.Array,
    B_t: jax.Array,
    C_t: jax.Array,
    D: jax.Array | None = None,
    dt_bias: jax.Array | None = None,
    dt_softplus: bool = True,
    state_mask: jax.Array | None = None,
):
    """O(1)-per-token recurrent step for decode (Mamba-2 shapes).

    Equivalent of ``selective_state_update`` applied to the multi-head SSD
    state.  ssm_state (b, h, p, n); x_t (b, h, p); dt_t (b, h);
    B_t/C_t (b, g, n).  Returns (y_t (b, h, p), new_state).

    ``state_mask`` (b,) bool: rows where it is False get their state back
    unchanged, from the expression that writes the others' new one (their
    ``y_t`` is then read from the old state and means nothing).  ``None``
    advances every row.
    """
    b, h, p, n = ssm_state.shape
    sf = ssm_state.astype(jnp.float32)
    xf = x_t.astype(jnp.float32)
    dtf = dt_t.astype(jnp.float32)
    if dt_bias is not None:
        dtf = dtf + dt_bias.astype(jnp.float32)
    if dt_softplus:
        dtf = jax.nn.softplus(dtf)
    Bh = _expand_groups(B_t[:, None], h)[:, 0].astype(jnp.float32)  # (b, h, n)
    Ch = _expand_groups(C_t[:, None], h)[:, 0].astype(jnp.float32)
    decay = jnp.exp(dtf * A.astype(jnp.float32)[None])  # (b, h)
    s = sf * decay[:, :, None, None] + jnp.einsum("bhp,bhn,bh->bhpn", xf, Bh, dtf)
    if state_mask is not None:
        s = jnp.where(state_mask[:, None, None, None], s, sf)
    y = jnp.einsum("bhpn,bhn->bhp", s, Ch)
    if D is not None:
        Df = D.astype(jnp.float32)
        y = y + xf * (Df[None] if Df.ndim == 2 else Df[None, :, None])
    return y.astype(x_t.dtype), s
