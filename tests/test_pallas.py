"""Pallas SSD kernel parity vs the XLA path (interpret mode on CPU; the
same kernels compile for real on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mamba_distributed_tpu.ops.pallas import ssd_chunked_pallas
from mamba_distributed_tpu.ops.ssd import ssd_chunked


def inputs(rng, b=2, t=128, h=4, p=64, n=128, g=1):
    ks = jax.random.split(rng, 5)
    x = jax.random.normal(ks[0], (b, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    B = jax.random.normal(ks[3], (b, t, g, n))
    C = jax.random.normal(ks[4], (b, t, g, n))
    D = jnp.ones((h,))
    return x, dt, A, B, C, D


@pytest.mark.parametrize("g", [1, 2])
@pytest.mark.parametrize("chunk", [32, 64])
def test_pallas_fwd_matches_xla(rng, g, chunk):
    x, dt, A, B, C, D = inputs(rng, g=g)
    ref = ssd_chunked(x, dt, A, B, C, chunk_size=chunk, D=D,
                      compute_dtype=jnp.float32)
    got = ssd_chunked_pallas(x, dt, A, B, C, chunk_size=chunk, D=D,
                             compute_dtype=jnp.float32, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.slow  # ~5s interpret-mode run: tier-1 wall-clock budget
def test_pallas_small_headdim(rng):
    """headdim 32 -> 4 heads per block; head blocking must stay exact."""
    x, dt, A, B, C, D = inputs(rng, h=8, p=32, n=64, g=2)
    ref = ssd_chunked(x, dt, A, B, C, chunk_size=32, D=None,
                      compute_dtype=jnp.float32)
    got = ssd_chunked_pallas(x, dt, A, B, C, chunk_size=32, D=None,
                             compute_dtype=jnp.float32, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.slow  # ~5s interpret-mode run: tier-1 wall-clock budget
def test_pallas_final_state_and_initial_state(rng):
    """State splicing: run halves with carried state == full run."""
    x, dt, A, B, C, D = inputs(rng, t=128)
    full, s_full = ssd_chunked_pallas(
        x, dt, A, B, C, chunk_size=32, compute_dtype=jnp.float32,
        return_final_state=True, interpret=True,
    )
    y1, s1 = ssd_chunked_pallas(
        x[:, :64], dt[:, :64], A, B[:, :64], C[:, :64], chunk_size=32,
        compute_dtype=jnp.float32, return_final_state=True, interpret=True,
    )
    y2, s2 = ssd_chunked_pallas(
        x[:, 64:], dt[:, 64:], A, B[:, 64:], C[:, 64:], chunk_size=32,
        compute_dtype=jnp.float32, initial_state=s1,
        return_final_state=True, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([y1, y2], axis=1)), np.asarray(full),
        atol=1e-4, rtol=1e-4,
    )
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.slow  # 15-25s interpret-mode run: keeps the tier-1
# 'not slow' sweep inside its wall-clock budget (the faster kernel
# parity tests below still run there)
def test_model_with_pallas_impl_matches_xla(rng):
    """ssm_impl='pallas' is a drop-in at the model level: same loss/grads."""
    from mamba_distributed_tpu.config import ModelConfig
    from mamba_distributed_tpu.models import init_lm_params, lm_loss

    kw = dict(d_model=32, n_layer=2, vocab_size=64, ssm_layer="mamba2",
              headdim=8, chunk_size=16, d_state=16, compute_dtype="float32")
    cfg_x = ModelConfig(**kw, ssm_impl="xla")
    cfg_p = ModelConfig(**kw, ssm_impl="pallas")
    params = init_lm_params(jax.random.PRNGKey(0), cfg_x)
    x = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
    y = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, 64)
    lx, gx = jax.value_and_grad(lm_loss)(params, cfg_x, x, y)
    lp, gp = jax.value_and_grad(lm_loss)(params, cfg_p, x, y)
    np.testing.assert_allclose(float(lp), float(lx), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(gx), jax.tree.leaves(gp)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=1e-4, rtol=1e-3)


@pytest.mark.slow
def test_pallas_under_sharded_train_step(tmp_path):
    """ssm_impl='pallas' inside the dp8-sharded jitted train step computes
    the same losses as the single-device XLA path."""
    from mamba_distributed_tpu.config import MeshConfig
    from tests.test_parallel import TINY_MODEL, losses_of

    ref, _ = losses_of(tmp_path / "a", steps=2, micro=8)
    saved = dict(TINY_MODEL)
    TINY_MODEL["ssm_impl"] = "pallas"
    try:
        pal, _ = losses_of(
            tmp_path / "b", mesh=MeshConfig(data=8), micro=1, steps=2
        )
    finally:
        TINY_MODEL.clear()
        TINY_MODEL.update(saved)
    np.testing.assert_allclose(ref, pal, rtol=2e-4)


def test_ssm_impl_validation():
    from mamba_distributed_tpu.config import ModelConfig

    with pytest.raises(ValueError, match="ssm_impl"):
        ModelConfig(ssm_impl="Pallas")
    # both mixers have a pallas backend
    ModelConfig(ssm_impl="pallas", ssm_layer="mamba1")
    ModelConfig(ssm_impl="pallas", ssm_layer="mamba2")


# ---------------------------------------------------------------------------
# Mamba-1 selective-scan kernel
# ---------------------------------------------------------------------------


def m1_inputs(rng, b=2, t=64, d=256, n=16):
    ks = jax.random.split(rng, 7)
    u = jax.random.normal(ks[0], (b, t, d))
    delta = jax.random.normal(ks[1], (b, t, d)) * 0.5
    A = -jnp.exp(jax.random.normal(ks[2], (d, n)) * 0.3)
    B = jax.random.normal(ks[3], (b, t, n))
    C = jax.random.normal(ks[4], (b, t, n))
    D = jnp.ones((d,))
    z = jax.random.normal(ks[5], (b, t, d))
    bias = jax.random.normal(ks[6], (d,)) * 0.1
    return u, delta, A, B, C, D, z, bias


@pytest.mark.slow  # ~5s interpret-mode run: tier-1 wall-clock budget
def test_m1_pallas_fwd_matches_oracle(rng):
    from mamba_distributed_tpu.ops.pallas import selective_scan_pallas
    from mamba_distributed_tpu.ops.scan import selective_scan_seq

    u, delta, A, B, C, D, z, bias = m1_inputs(rng)
    ref = selective_scan_seq(u, delta, A, B, C, D=D, z=z, delta_bias=bias,
                             delta_softplus=True)
    got = selective_scan_pallas(u, delta, A, B, C, D=D, z=z, delta_bias=bias,
                                delta_softplus=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_m1_pallas_odd_d(rng):
    """d with no 128-multiple divisor exercises the block-size fallback."""
    u, delta, A, B, C, D, z, bias = m1_inputs(rng, d=96)
    from mamba_distributed_tpu.ops.pallas import selective_scan_pallas
    from mamba_distributed_tpu.ops.scan import selective_scan_seq

    ref = selective_scan_seq(u, delta, A, B, C, D=D, delta_softplus=True)
    got = selective_scan_pallas(u, delta, A, B, C, D=D, delta_softplus=True,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_m1_pallas_multiple_time_tiles(rng, monkeypatch):
    """Force nt > 1 so the scratch-carried state crosses t-tile boundaries
    (long sequences stream through a bounded VMEM budget this way)."""
    from mamba_distributed_tpu.ops.pallas import scan_kernels
    from mamba_distributed_tpu.ops.scan import selective_scan_seq

    monkeypatch.setattr(scan_kernels, "_pick_blocks", lambda t, d: (16, 128))
    u, delta, A, B, C, D, z, bias = m1_inputs(rng, t=64, d=128)
    ref = selective_scan_seq(u, delta, A, B, C, D=D, delta_softplus=True)
    got = scan_kernels.selective_scan_pallas(
        u, delta, A, B, C, D=D, delta_softplus=True, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_m1_pallas_state_splicing(rng):
    from mamba_distributed_tpu.ops.pallas import selective_scan_pallas

    u, delta, A, B, C, D, z, bias = m1_inputs(rng, t=64)
    full, s_full = selective_scan_pallas(
        u, delta, A, B, C, delta_softplus=True,
        return_final_state=True, interpret=True,
    )
    y1, s1 = selective_scan_pallas(
        u[:, :32], delta[:, :32], A, B[:, :32], C[:, :32],
        delta_softplus=True, return_final_state=True, interpret=True,
    )
    y2, s2 = selective_scan_pallas(
        u[:, 32:], delta[:, 32:], A, B[:, 32:], C[:, 32:],
        delta_softplus=True, initial_state=s1,
        return_final_state=True, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([y1, y2], axis=1)), np.asarray(full),
        atol=1e-4, rtol=1e-4,
    )
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.slow  # 7-10s interpret-mode run: keeps tier-1 'not slow'
# inside its wall-clock budget (fwd-parity coverage stays in tier-1)
def test_m1_pallas_grads_match_xla(rng):
    from mamba_distributed_tpu.ops.pallas import selective_scan_pallas
    from mamba_distributed_tpu.ops.scan import selective_scan

    u, delta, A, B, C, D, z, bias = m1_inputs(rng, t=32, d=128)

    def loss(fn, interp):
        def inner(u, delta, A, B, C):
            kw = dict(D=D, z=z[:, :32], delta_bias=bias, delta_softplus=True)
            if interp:
                kw["interpret"] = True
            return jnp.sum(fn(u, delta, A, B, C, **kw) ** 2)

        return inner

    g_ref = jax.grad(loss(selective_scan, False), argnums=(0, 1, 2, 3, 4))(
        u[:, :32], delta[:, :32], A, B[:, :32], C[:, :32]
    )
    g_pal = jax.grad(loss(selective_scan_pallas, True), argnums=(0, 1, 2, 3, 4))(
        u[:, :32], delta[:, :32], A, B[:, :32], C[:, :32]
    )
    for a, b in zip(g_ref, g_pal):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=2e-3, rtol=2e-3)


@pytest.mark.slow  # 7-10s interpret-mode run: keeps tier-1 'not slow'
# inside its wall-clock budget (fwd-parity coverage stays in tier-1)
def test_m1_pallas_grads_seeded_and_final_state(rng):
    """Seeded m1 path (initial_state in, final state out) differentiates
    through the Pallas custom_vjp — including dfinal seeding the reverse
    sweep and the initial-state gradient — matching XLA autodiff."""
    from mamba_distributed_tpu.ops.pallas import selective_scan_pallas
    from mamba_distributed_tpu.ops.scan import selective_scan

    u, delta, A, B, C, D, z, bias = m1_inputs(rng, t=64, d=96)  # pad path too
    h0 = jax.random.normal(jax.random.PRNGKey(9),
                           (u.shape[0], u.shape[2], A.shape[-1]))

    def loss(fn, **kw):
        def inner(u, delta, A, B, C, h0):
            y, fin = fn(u, delta, A, B, C, D=D, z=z, delta_bias=bias,
                        delta_softplus=True, initial_state=h0,
                        return_final_state=True, **kw)
            return jnp.sum(y ** 2) + 0.5 * jnp.sum(fin ** 2)
        return inner

    args = (u, delta, A, B, C, h0)
    g_ref = jax.grad(loss(selective_scan), argnums=tuple(range(6)))(*args)
    g_pal = jax.grad(loss(selective_scan_pallas, interpret=True),
                     argnums=tuple(range(6)))(*args)
    for a, b in zip(g_ref, g_pal):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=2e-3, rtol=2e-3)


@pytest.mark.slow  # 7-10s interpret-mode run: keeps tier-1 'not slow'
# inside its wall-clock budget (fwd-parity coverage stays in tier-1)
def test_m1_model_with_pallas_impl_matches_xla(rng):
    """ssm_impl='pallas' is a drop-in for the mamba1 LM: same loss/grads."""
    from mamba_distributed_tpu.config import ModelConfig
    from mamba_distributed_tpu.models import init_lm_params, lm_loss

    kw = dict(d_model=32, n_layer=2, vocab_size=64, ssm_layer="mamba1",
              d_state=8, compute_dtype="float32")
    cfg_x = ModelConfig(**kw, ssm_impl="xla")
    cfg_p = ModelConfig(**kw, ssm_impl="pallas")
    params = init_lm_params(jax.random.PRNGKey(0), cfg_x)
    x = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
    y = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, 64)
    lx, gx = jax.value_and_grad(lm_loss)(params, cfg_x, x, y)
    lp, gp = jax.value_and_grad(lm_loss)(params, cfg_p, x, y)
    np.testing.assert_allclose(float(lp), float(lx), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(gx), jax.tree.leaves(gp)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=1e-4, rtol=1e-3)


@pytest.mark.slow  # 15-25s interpret-mode run: keeps the tier-1
# 'not slow' sweep inside its wall-clock budget (the faster kernel
# parity tests below still run there)
def test_pallas_grads_match_xla(rng):
    """Pallas custom_vjp backward == XLA autodiff grads of ssd_chunked."""
    x, dt, A, B, C, D = inputs(rng, t=64)

    def loss_ref(x, dt, A, B, C):
        return jnp.sum(
            ssd_chunked(x, dt, A, B, C, chunk_size=32,
                        compute_dtype=jnp.float32) ** 2
        )

    def loss_pal(x, dt, A, B, C):
        return jnp.sum(
            ssd_chunked_pallas(x, dt, A, B, C, chunk_size=32,
                               compute_dtype=jnp.float32, interpret=True) ** 2
        )

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2, 3, 4))(x, dt, A, B, C)
    g_pal = jax.grad(loss_pal, argnums=(0, 1, 2, 3, 4))(x, dt, A, B, C)
    for a, b in zip(g_ref, g_pal):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=2e-3, rtol=2e-3)


@pytest.mark.slow  # 15-25s interpret-mode run: keeps the tier-1
# 'not slow' sweep inside its wall-clock budget (the faster kernel
# parity tests below still run there)
def test_pallas_grads_grouped_small_headdim(rng):
    """Backward with g=2 groups and headdim 32 (4 heads per block): the
    per-head-block dB/dC partials must group-sum correctly."""
    x, dt, A, B, C, D = inputs(rng, t=96, h=8, p=32, n=64, g=2)

    def loss(fn, **kw):
        def inner(x, dt, A, B, C):
            return jnp.sum(fn(x, dt, A, B, C, chunk_size=32,
                              compute_dtype=jnp.float32, **kw) ** 2)
        return inner

    g_ref = jax.grad(loss(ssd_chunked), argnums=(0, 1, 2, 3, 4))(x, dt, A, B, C)
    g_pal = jax.grad(loss(ssd_chunked_pallas, interpret=True),
                     argnums=(0, 1, 2, 3, 4))(x, dt, A, B, C)
    for a, b in zip(g_ref, g_pal):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=2e-3, rtol=2e-3)


def test_pallas_grads_seeded_and_final_state(rng):
    """The seeded path (initial_state in, final state out — the SP shard /
    decode-prefill shape) must be differentiable through the Pallas
    custom_vjp, including the initial-state gradient, and match XLA
    autodiff of ssd_chunked."""
    x, dt, A, B, C, D = inputs(rng, t=64)
    s0 = jax.random.normal(jax.random.PRNGKey(7),
                           (x.shape[0], x.shape[2], x.shape[3], C.shape[-1]))

    def loss(fn, **kw):
        def inner(x, dt, A, B, C, s0):
            y, fin = fn(x, dt, A, B, C, chunk_size=32,
                        compute_dtype=jnp.float32, initial_state=s0,
                        return_final_state=True, **kw)
            # weight final-state so its cotangent is nonzero and distinct
            return jnp.sum(y ** 2) + 0.5 * jnp.sum(fin ** 2)
        return inner

    args = (x, dt, A, B, C, s0)
    g_ref = jax.grad(loss(ssd_chunked), argnums=tuple(range(6)))(*args)
    g_pal = jax.grad(loss(ssd_chunked_pallas, interpret=True),
                     argnums=tuple(range(6)))(*args)
    for a, b in zip(g_ref, g_pal):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=2e-3, rtol=2e-3)


def test_pallas_grads_initial_state_no_final(rng):
    """Seeded forward without returning the final state (prefill-into-loss
    shape): dinit must still flow."""
    x, dt, A, B, C, D = inputs(rng, t=64)
    s0 = jax.random.normal(jax.random.PRNGKey(3),
                           (x.shape[0], x.shape[2], x.shape[3], C.shape[-1]))

    def loss(fn, **kw):
        def inner(s0):
            y = fn(x, dt, A, B, C, chunk_size=32, compute_dtype=jnp.float32,
                   initial_state=s0, **kw)
            return jnp.sum(y ** 2)
        return inner

    g_ref = jax.grad(loss(ssd_chunked))(s0)
    g_pal = jax.grad(loss(ssd_chunked_pallas, interpret=True))(s0)
    np.testing.assert_allclose(np.asarray(g_pal), np.asarray(g_ref),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.slow  # 15-25s interpret-mode run: keeps the tier-1
# 'not slow' sweep inside its wall-clock budget (the faster kernel
# parity tests below still run there)
def test_pallas_bwd_small_headdim_large_chunk(rng):
    """p=8 with l=256 was the ADVICE-r3 VMEM blowup case under head
    blocking; with the round-4 one-head-per-cell kernels the backward's
    (l, l) working set is hb-independent — this pins that the shape
    still runs and matches XLA grads."""
    x, dt, A, B, C, _ = inputs(rng, b=1, t=512, h=16, p=8, n=64, g=1)

    def loss(fn, **kw):
        def inner(x, dt, A, B, C):
            return jnp.sum(fn(x, dt, A, B, C, chunk_size=256,
                              compute_dtype=jnp.float32, **kw) ** 2)
        return inner

    g_ref = jax.grad(loss(ssd_chunked), argnums=(0, 1, 2, 3, 4))(x, dt, A, B, C)
    g_pal = jax.grad(loss(ssd_chunked_pallas, interpret=True),
                     argnums=(0, 1, 2, 3, 4))(x, dt, A, B, C)
    for a, b in zip(g_ref, g_pal):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        scale = max(1.0, float(np.abs(a).max()))
        np.testing.assert_allclose(b / scale, a / scale, atol=5e-3)


@pytest.mark.slow  # 7-10s interpret-mode run: keeps tier-1 'not slow'
# inside its wall-clock budget (fwd-parity coverage stays in tier-1)
def test_pallas_grads_with_D_and_bf16(rng):
    """Training-shaped call: D skip + bf16 compute; grads stay close to the
    XLA path under the same compute dtype."""
    x, dt, A, B, C, D = inputs(rng, t=128)

    def loss(fn, **kw):
        def inner(x, dt, A, B, C):
            y = fn(x, dt, A, B, C, chunk_size=64, D=D,
                   compute_dtype=jnp.bfloat16, **kw)
            return jnp.sum(y.astype(jnp.float32) ** 2)
        return inner

    g_ref = jax.grad(loss(ssd_chunked), argnums=(0, 1, 2, 3, 4))(x, dt, A, B, C)
    g_pal = jax.grad(loss(ssd_chunked_pallas, interpret=True),
                     argnums=(0, 1, 2, 3, 4))(x, dt, A, B, C)
    for a, b in zip(g_ref, g_pal):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        scale = max(1.0, float(np.abs(a).max()))
        np.testing.assert_allclose(b / scale, a / scale, atol=4e-2)


# ---------------------------------------------------------------------------
# TPU-platform lowering (no chip needed): jax.export runs the REAL
# Pallas->Mosaic lowering path, catching BlockSpec tiling violations and
# unsupported-op errors that interpret mode never sees.
# ---------------------------------------------------------------------------


def _export_tpu(fn, *args):
    return jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)


@pytest.mark.parametrize("shapes", [
    dict(),                                 # default: h=4, p=64, g=1
    dict(h=8, p=32, n=64, g=2),             # grouped + small headdim
    dict(h=6, p=64, g=2),                   # odd head count per group
])
def test_ssd_tpu_lowering_fwd_and_grad(rng, shapes):
    x, dt, A, B, C, D = inputs(rng, t=128, **shapes)

    def f(x, dt, A, B, C):
        return ssd_chunked_pallas(x, dt, A, B, C, chunk_size=64, D=D,
                                  compute_dtype=jnp.bfloat16, interpret=False)

    _export_tpu(f, x, dt, A, B, C)
    _export_tpu(
        jax.grad(lambda *a: jnp.sum(f(*a).astype(jnp.float32) ** 2),
                 (0, 1, 2, 3, 4)),
        x, dt, A, B, C,
    )


def test_m1_tpu_lowering_fwd_and_grad(rng):
    from mamba_distributed_tpu.ops.pallas import selective_scan_pallas

    u, delta, A, B, C, D, z, bias = m1_inputs(rng, t=64, d=96)  # odd d: pad path

    def f(u, delta, A, B, C):
        return selective_scan_pallas(u, delta, A, B, C, D=D, z=z,
                                     delta_bias=bias, delta_softplus=True,
                                     interpret=False)

    _export_tpu(f, u, delta, A, B, C)
    _export_tpu(
        jax.grad(lambda *a: jnp.sum(f(*a) ** 2), (0, 1, 2, 3, 4)),
        u, delta, A, B, C,
    )


def test_m1_tpu_lowering_seeded_grad(rng):
    """The seeded custom_vjp (dfinal-seeded reverse sweep + dh0 output)
    Mosaic-lowers for the TPU platform."""
    from mamba_distributed_tpu.ops.pallas import selective_scan_pallas

    u, delta, A, B, C, D, z, bias = m1_inputs(rng, t=64, d=96)
    h0 = jax.random.normal(jax.random.PRNGKey(2),
                           (u.shape[0], u.shape[2], A.shape[-1]))

    def loss(u, delta, A, B, C, h0):
        y, fin = selective_scan_pallas(
            u, delta, A, B, C, D=D, delta_bias=bias, delta_softplus=True,
            initial_state=h0, return_final_state=True, interpret=False,
        )
        return jnp.sum(y ** 2) + jnp.sum(fin ** 2)

    _export_tpu(jax.grad(loss, tuple(range(6))), u, delta, A, B, C, h0)


@pytest.mark.slow  # 4-10s each: the PR-8 shard_map shim un-failed
# this case into tier-1; the wall-clock budget keeps only the fastest
# re-enabled cases in 'not slow' (run the full set via -m slow)
def test_seq_sharded_train_step_tpu_lowering(monkeypatch, tmp_path):
    """The FULL seq-sharded train step with pallas mixers (the sp_ssd
    pallas route) lowers for the TPU platform — forced through the real
    Mosaic path via MDT_PALLAS_INTERPRET=0, so shard_map + ppermute +
    Pallas custom_vjp compose in one exported program (VERDICT r3 #3)."""
    monkeypatch.setenv("MDT_PALLAS_INTERPRET", "0")
    from mamba_distributed_tpu.config import (
        DataConfig,
        MeshConfig,
        ModelConfig,
        TrainConfig,
    )
    from mamba_distributed_tpu.training import Trainer

    model = ModelConfig(
        d_model=64, n_layer=2, vocab_size=256, ssm_layer="mamba2",
        headdim=16, chunk_size=16, d_state=32, ssm_impl="pallas",
    )
    B, T, accum = 2, 64, 2
    cfg = TrainConfig(
        model=model,
        mesh=MeshConfig(seq=4),
        data=DataConfig(
            data_dir=str(tmp_path / "data"),
            synthetic_tokens_per_shard=B * T * accum * 8,
            synthetic_num_shards=1,
        ),
        micro_batch_size=B,
        seq_len=T,
        total_batch_size=B * T * accum,
        log_dir=str(tmp_path / "log"),
        warmup_steps=2,
        max_steps=4,
        val_every=1000,
    )
    trainer = Trainer(cfg, verbose=False)
    x, y = trainer._global_batch(cfg.grad_accum_steps, trainer.train_loader)
    exported = jax.export.export(trainer.train_step, platforms=["tpu"])(
        trainer.params, trainer.opt_state, x, y
    )
    assert "tpu" in [p.lower() for p in exported.platforms]


@pytest.mark.slow  # 4-10s each: the PR-8 shard_map shim un-failed
# this case into tier-1; the wall-clock budget keeps only the fastest
# re-enabled cases in 'not slow' (run the full set via -m slow)
def test_hybrid_ring_flash_train_step_tpu_lowering(monkeypatch, tmp_path):
    """Seq-sharded HYBRID train step with attn_impl='pallas': shard_map +
    lax.switch over the flash pair kernels + the ring custom_vjp (dk/dv
    riding the ring) all compose in one TPU-exported program."""
    monkeypatch.setenv("MDT_PALLAS_INTERPRET", "0")
    from mamba_distributed_tpu.config import (
        DataConfig,
        MeshConfig,
        ModelConfig,
        TrainConfig,
    )
    from mamba_distributed_tpu.training import Trainer

    model = ModelConfig(
        d_model=64, n_layer=2, vocab_size=256, ssm_layer="mamba2",
        headdim=16, chunk_size=16, d_state=32, attn_layer_idx=(1,),
        attn_num_heads=4, attn_num_kv_heads=2, attn_impl="pallas",
    )
    B, T, accum = 2, 64, 2
    cfg = TrainConfig(
        model=model,
        mesh=MeshConfig(seq=4),
        data=DataConfig(
            data_dir=str(tmp_path / "data"),
            synthetic_tokens_per_shard=B * T * accum * 8,
            synthetic_num_shards=1,
        ),
        micro_batch_size=B,
        seq_len=T,
        total_batch_size=B * T * accum,
        log_dir=str(tmp_path / "log"),
        warmup_steps=2,
        max_steps=4,
        val_every=1000,
    )
    trainer = Trainer(cfg, verbose=False)
    x, y = trainer._global_batch(cfg.grad_accum_steps, trainer.train_loader)
    exported = jax.export.export(trainer.train_step, platforms=["tpu"])(
        trainer.params, trainer.opt_state, x, y
    )
    assert "tpu" in [p.lower() for p in exported.platforms]


@pytest.mark.parametrize("layer,kw", [
    ("mamba2", dict(headdim=16, chunk_size=32, d_state=32)),
    ("mamba1", dict(d_state=8)),
])
def test_full_model_grad_tpu_lowering_pallas(layer, kw):
    """The COMPOSED training graph (embed -> blocks with pallas mixers ->
    loss -> grad) lowers for the TPU platform end to end."""
    from mamba_distributed_tpu.config import ModelConfig
    from mamba_distributed_tpu.models import init_lm_params, lm_loss

    cfg = ModelConfig(d_model=64, n_layer=2, vocab_size=256, ssm_layer=layer,
                      ssm_impl="pallas", **kw)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((2, 64), jnp.int32)
    y = jnp.zeros((2, 64), jnp.int32)
    _export_tpu(
        lambda p, x, y: jax.value_and_grad(lm_loss)(p, cfg, x, y),
        params, x, y,
    )
