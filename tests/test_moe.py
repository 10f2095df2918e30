"""MoE MLP + expert parallelism (beyond the reference: completes the
parallelism menu with the `expert` mesh axis)."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mamba_distributed_tpu.config import (
    DataConfig,
    MeshConfig,
    ModelConfig,
    TrainConfig,
)
from mamba_distributed_tpu.models import init_lm_params, lm_loss
from mamba_distributed_tpu.models.lm import (
    _gated_mlp,
    _moe_mlp,
    count_params,
    lm_forward,
)

MOE_KW = dict(
    d_model=32, n_layer=2, vocab_size=64, ssm_layer="mamba2", headdim=8,
    chunk_size=16, d_state=16, compute_dtype="float32",
    d_intermediate=48, moe_num_experts=4,
)


def test_identical_experts_match_dense(rng):
    """With every expert holding the SAME weights, the top-k mixture must
    equal the dense gated MLP (the gates sum to 1) — the routing/grouping/
    combine algebra's exact oracle."""
    cfg = ModelConfig(**MOE_KW)
    d, di, E = cfg.d_model, cfg.d_intermediate, cfg.moe_num_experts
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    w1 = jax.random.normal(k1, (d, 2 * di)) * 0.1
    w2 = jax.random.normal(k2, (di, d)) * 0.1
    params = {
        "router": {"kernel": jax.random.normal(k3, (d, E))},
        "w1": jnp.broadcast_to(w1, (E, d, 2 * di)),
        "w2": jnp.broadcast_to(w2, (E, di, d)),
    }
    x = jax.random.normal(k4, (2, 16, d))
    dense = _gated_mlp({"fc1": {"kernel": w1}, "fc2": {"kernel": w2}},
                       x, jnp.float32)
    out, aux, _ = _moe_mlp(params, cfg, x, jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               atol=1e-5, rtol=1e-5)
    assert np.isfinite(float(aux))


def test_aux_loss_is_one_at_perfect_balance(rng):
    """Uniform router -> f_e = P_e = 1/E -> aux == 1 (the Switch floor)."""
    cfg = ModelConfig(**MOE_KW, moe_top_k=1)
    d, di, E = cfg.d_model, cfg.d_intermediate, cfg.moe_num_experts
    params = {
        "router": {"kernel": jnp.zeros((d, E))},
        "w1": jnp.zeros((E, d, 2 * di)),
        "w2": jnp.zeros((E, di, d)),
    }
    x = jax.random.normal(rng, (2, 32, d))
    _, aux, _ = _moe_mlp(params, cfg, x, jnp.float32)
    np.testing.assert_allclose(float(aux), 1.0, rtol=1e-6)


@pytest.mark.parametrize("rows", [8, 512])
def test_nothing_is_dropped_at_any_load(rng, rows, monkeypatch):
    """Every row routed to ONE expert (the load the old capacity dropped
    at): the layer must answer each row with that expert's whole product
    under gate 1, in the masked form (few rows) and the grouped one (many),
    and gradients must flow through the model with that router."""
    from mamba_distributed_tpu.models import lm as lm_mod

    monkeypatch.setattr(lm_mod, "MOE_DENSE_MAX_ROWS", 64)
    cfg = ModelConfig(**MOE_KW, moe_top_k=1)
    d, di, E = cfg.d_model, cfg.d_intermediate, cfg.moe_num_experts
    k1, k2, k3 = jax.random.split(rng, 3)
    w1 = jax.random.normal(k1, (E, d, 2 * di)) * 0.1
    w2 = jax.random.normal(k2, (E, di, d)) * 0.1
    # positive inputs and a router whose column 2 is largest: all to 2
    x = jnp.abs(jax.random.normal(k3, (1, rows, d))) + 0.1
    router = jnp.zeros((d, E)).at[:, 2].set(1.0)
    out, _, load = _moe_mlp(
        {"router": {"kernel": router}, "w1": w1, "w2": w2}, cfg, x,
        jnp.float32)
    want = _gated_mlp({"fc1": {"kernel": w1[2]}, "fc2": {"kernel": w2[2]}},
                      x, jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    # a held expert's rows, then the held experts reached
    assert np.asarray(load).tolist() == [0, 0, rows, 0, 1]

    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(rng, (2, 32), 0, cfg.vocab_size)
    tgt = jax.random.randint(jax.random.fold_in(rng, 1), (2, 32), 0,
                             cfg.vocab_size)
    loss, grads = jax.value_and_grad(lm_loss)(params, cfg, ids, tgt)
    assert np.isfinite(float(loss))
    router_g = grads["blocks"]["moe"]["router"]["kernel"]
    assert float(jnp.max(jnp.abs(router_g))) > 0  # router learns
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))


def test_moe_param_count_matches_analytic():
    cfg = ModelConfig(**MOE_KW)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    assert count_params(params) == cfg.num_params()


def test_moe_decode_matches_forward(rng):
    """O(1) decode through the MoE layer == full-forward logits."""
    from mamba_distributed_tpu.models.lm import lm_prefill, lm_step

    cfg = ModelConfig(**MOE_KW, remat=False)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(rng, (2, 17), 0, cfg.vocab_size)

    ref = lm_forward(params, cfg, ids)
    logits_pre, state = lm_prefill(params, cfg, ids[:, :-1], max_len=17)
    step_logits, _ = lm_step(params, cfg, state, ids[:, -1])
    np.testing.assert_allclose(
        np.asarray(step_logits), np.asarray(ref[:, -1]), atol=2e-4, rtol=2e-3
    )


def test_moe_aux_reaches_loss(rng):
    """lm_loss includes moe_aux_weight * aux: weight 0 vs big weight must
    move the loss."""
    cfg0 = ModelConfig(**MOE_KW, moe_aux_weight=0.0)
    cfg1 = ModelConfig(**MOE_KW, moe_aux_weight=10.0)
    params = init_lm_params(jax.random.PRNGKey(0), cfg0)
    ids = jax.random.randint(rng, (2, 32), 0, cfg0.vocab_size)
    tgt = jax.random.randint(jax.random.fold_in(rng, 1), (2, 32), 0,
                             cfg0.vocab_size)
    l0 = float(lm_loss(params, cfg0, ids, tgt))
    l1 = float(lm_loss(params, cfg1, ids, tgt))
    assert l1 > l0 + 1.0  # aux >= 1 by Cauchy-Schwarz, weight 10 shows up


def test_config_rejects_bad_moe():
    with pytest.raises(ValueError, match="d_intermediate"):
        ModelConfig(moe_num_experts=4)
    with pytest.raises(ValueError, match="moe_top_k"):
        ModelConfig(d_intermediate=8, moe_num_experts=4, moe_top_k=5)
    with pytest.raises(ValueError, match="mesh.expert"):
        TrainConfig(
            model=ModelConfig(), mesh=MeshConfig(expert=2),
            micro_batch_size=1, seq_len=64, total_batch_size=128,
        )


def _trainer_losses(tmp, mesh, micro, steps=3):
    from mamba_distributed_tpu.training import Trainer

    model = ModelConfig(**MOE_KW)
    dp = mesh.data * mesh.fsdp * mesh.expert
    cfg = TrainConfig(
        model=model,
        mesh=mesh,
        data=DataConfig(
            data_dir=os.path.join(str(tmp), "data"),
            synthetic_tokens_per_shard=50_000,
            synthetic_num_shards=2,
        ),
        micro_batch_size=micro,
        seq_len=64,
        total_batch_size=micro * 64 * dp * 2,
        log_dir=os.path.join(str(tmp), "log"),
        warmup_steps=2,
        max_steps=100,
        val_every=1000,
    )
    t = Trainer(cfg, verbose=False)
    out = []
    for _ in range(steps):
        x, y = t._global_batch(cfg.grad_accum_steps, t.train_loader)
        t.params, t.opt_state, loss, _ = t.train_step(
            t.params, t.opt_state, x, y
        )
        out.append(float(loss))
    return out, t


@pytest.mark.slow
def test_expert_parallel_matches_single_device(tmp_path):
    """mesh.expert=4 (experts sharded + tokens batch-sharded over the
    expert axis) == single-device losses: the GSPMD all-to-all
    formulation of dispatch/combine is exact."""
    ref, _ = _trainer_losses(tmp_path / "a", MeshConfig(), micro=8)
    ep, tr = _trainer_losses(tmp_path / "b", MeshConfig(expert=4), micro=2)
    np.testing.assert_allclose(ref, ep, rtol=2e-4)
    spec = tr.params["blocks"]["moe"]["w1"].sharding.spec
    assert spec and spec[1] == "expert", spec


@pytest.mark.slow
def test_expert_x_data_parallel_matches_single_device(tmp_path):
    """mesh (data=2, expert=2) composes: both act as batch axes for the
    dense layers, experts shard over the expert axis.

    rtol covers the (data x expert) layout's gradient-psum
    re-association: the 2-D mesh reduces microbatch partials in a
    different order than one device, and after 3 optimizer steps the
    divergence compounds to ~5e-4 relative on the loss (measured
    standalone; a shared-process run can land closer and did, which is
    why the old 2e-4 passed in the full tier and failed alone)."""
    ref, _ = _trainer_losses(tmp_path / "a", MeshConfig(), micro=8)
    ep, _ = _trainer_losses(
        tmp_path / "b", MeshConfig(data=2, expert=2), micro=2
    )
    np.testing.assert_allclose(ref, ep, rtol=2e-3)
