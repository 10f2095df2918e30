"""Pallas flash-attention kernel parity vs the XLA blockwise path
(interpret mode on CPU; the same kernel compiles for real on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mamba_distributed_tpu.ops.blockwise_attention import blockwise_sdpa_causal
from mamba_distributed_tpu.ops.pallas.attention_kernels import flash_sdpa_causal


def qkv(rng, b=2, t=128, nh=4, nkv=4, hd=64, tk=None, dtype=jnp.float32):
    tk = t if tk is None else tk
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (b, t, nh, hd), dtype)
    k = jax.random.normal(ks[1], (b, tk, nkv, hd), dtype)
    v = jax.random.normal(ks[2], (b, tk, nkv, hd), dtype)
    return q, k, v


@pytest.mark.parametrize("shapes", [
    dict(),                                 # MHA
    dict(nh=8, nkv=2, hd=32),               # GQA
    dict(nh=4, nkv=1),                      # MQA
    dict(t=100),                            # q/k padding (100 -> 104)
    dict(t=320),                            # multiple q and kv blocks
])
def test_flash_fwd_matches_blockwise(rng, shapes):
    q, k, v = qkv(rng, **shapes)
    ref = blockwise_sdpa_causal(q, k, v)
    got = flash_sdpa_causal(q, k, v, q_block=64, k_block=64, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_flash_fwd_offset_decode_prefill(rng):
    """offset > 0 — q is a suffix continuing a longer KV prefix."""
    q, k, v = qkv(rng, t=64, tk=192)
    ref = blockwise_sdpa_causal(q, k, v, offset=128)
    got = flash_sdpa_causal(q, k, v, offset=128, q_block=64, k_block=64,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_flash_fwd_bf16(rng):
    q, k, v = qkv(rng, dtype=jnp.bfloat16)
    ref = blockwise_sdpa_causal(q, k, v)
    got = flash_sdpa_causal(q, k, v, q_block=64, k_block=64, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        atol=3e-2, rtol=3e-2,
    )


@pytest.mark.slow  # 5-20s interpret-mode run: keeps tier-1 'not slow'
# inside its wall-clock budget (fwd parity + lowering stay in tier-1)
@pytest.mark.parametrize("shapes", [
    dict(),
    dict(nh=8, nkv=2, hd=32),               # GQA partials group-summed
    dict(t=100),                            # padded rows must not NaN grads
])
def test_flash_grads_match_blockwise(rng, shapes):
    q, k, v = qkv(rng, **shapes)

    def loss(fn, extra=()):
        def inner(q, k, v):
            return jnp.sum(jnp.sin(fn(q, k, v, *extra)))
        return inner

    g_ref = jax.grad(loss(blockwise_sdpa_causal), argnums=(0, 1, 2))(q, k, v)
    g_pal = jax.grad(
        loss(lambda q, k, v: flash_sdpa_causal(
            q, k, v, q_block=64, k_block=64, interpret=True)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_ref, g_pal):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=2e-4, rtol=1e-3)


@pytest.mark.slow  # 5-20s interpret-mode run: keeps tier-1 'not slow'
# inside its wall-clock budget (fwd parity + lowering stay in tier-1)
def test_flash_model_drop_in(rng):
    """attn_impl='pallas' reproduces the XLA hybrid model exactly-ish."""
    from mamba_distributed_tpu.config import ModelConfig
    from mamba_distributed_tpu.models.lm import init_lm_params, lm_forward

    kw = dict(
        d_model=64, n_layer=2, vocab_size=512, ssm_layer="mamba2",
        headdim=32, d_state=64, chunk_size=32, attn_layer_idx=(1,),
        attn_num_heads=2, compute_dtype="float32",
    )
    cfg_x = ModelConfig(**kw)
    cfg_p = ModelConfig(**kw, attn_impl="pallas")
    params = init_lm_params(rng, cfg_x)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 512)

    def loss(cfg):
        def inner(params):
            logits = lm_forward(params, cfg, ids)
            return jnp.mean(logits.astype(jnp.float32) ** 2)
        return inner

    lx, gx = jax.value_and_grad(loss(cfg_x))(params)
    lp, gp = jax.value_and_grad(loss(cfg_p))(params)
    np.testing.assert_allclose(float(lp), float(lx), atol=1e-5, rtol=1e-5)
    for (ka, a), (kb, b) in zip(
        jax.tree_util.tree_leaves_with_path(gx),
        jax.tree_util.tree_leaves_with_path(gp),
    ):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=1e-4, rtol=1e-3,
                                   err_msg=str(ka))


# ---------------------------------------------------------------------------
# TPU-platform lowering (no chip needed): jax.export runs the REAL
# Pallas->Mosaic lowering path.  NOTE (round 4): this does NOT run Mosaic's
# infer-vector-layout pass — lane-splitting reshapes passed here but failed
# on hardware — so the kernels are written reshape/transpose-free and
# scripts/tpu_smoke.py re-checks on the real chip.
# ---------------------------------------------------------------------------


def _export_tpu(fn, *args):
    return jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)


@pytest.mark.parametrize("shapes", [
    dict(),
    dict(nh=8, nkv=2, hd=32),
    dict(t=100),
])
def test_flash_tpu_lowering_fwd_and_grad(rng, shapes):
    q, k, v = qkv(rng, dtype=jnp.bfloat16, **shapes)

    def f(q, k, v):
        return flash_sdpa_causal(q, k, v, q_block=64, k_block=64,
                                 interpret=False)

    _export_tpu(f, q, k, v)
    _export_tpu(
        jax.grad(lambda *a: jnp.sum(f(*a).astype(jnp.float32) ** 2),
                 (0, 1, 2)),
        q, k, v,
    )


def test_resolve_attn_impl_auto(monkeypatch):
    """auto -> xla on CPU hosts, pallas when MDT_PALLAS_INTERPRET=0 marks a
    chip-free TPU lowering (so exports bake in the hardware kernels)."""
    from mamba_distributed_tpu.ops.pallas.common import resolve_attn_impl

    monkeypatch.delenv("MDT_PALLAS_INTERPRET", raising=False)
    assert resolve_attn_impl("xla") == "xla"
    assert resolve_attn_impl("pallas") == "pallas"
    assert resolve_attn_impl("auto") == "xla"  # CPU test host
    monkeypatch.setenv("MDT_PALLAS_INTERPRET", "0")
    assert resolve_attn_impl("auto") == "pallas"
    monkeypatch.setenv("MDT_PALLAS_INTERPRET", "1")
    assert resolve_attn_impl("auto") == "xla"


def test_resolve_attn_impl_dedicated_env_override(monkeypatch):
    """MDT_ATTN_IMPL beats the MDT_PALLAS_INTERPRET heuristic (ADVICE r4:
    keep the interpret env var single-purpose), and rejects junk."""
    import pytest

    from mamba_distributed_tpu.ops.pallas.common import resolve_attn_impl

    monkeypatch.setenv("MDT_PALLAS_INTERPRET", "1")  # would say "xla"
    monkeypatch.setenv("MDT_ATTN_IMPL", "pallas")
    assert resolve_attn_impl("auto") == "pallas"
    monkeypatch.setenv("MDT_ATTN_IMPL", "xla")
    assert resolve_attn_impl("auto") == "xla"
    # explicit impl is never overridden by env
    assert resolve_attn_impl("pallas") == "pallas"
    monkeypatch.setenv("MDT_ATTN_IMPL", "triton")
    with pytest.raises(ValueError, match="MDT_ATTN_IMPL"):
        resolve_attn_impl("auto")
