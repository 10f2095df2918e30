"""benchmark/reference against the program at a tiny size, full float32."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import init as ref_init
from benchmark.reference import model as ref_model
from benchmark.reference import train as ref_train
from conftest import DATA


def _cfg(name):
    from mamba_distributed_tpu.config import get_preset

    c = json.load(open(os.path.join(DATA, "configs", name + ".json")))
    cfg = dataclasses.replace(get_preset(c["preset"]).model,
                              compute_dtype="float32", **c.get("serving", {}))
    return c, cfg


@pytest.mark.parametrize("name", ["tiny-mamba2", "tiny-hybrid"])
def test_forward_matches_lm_forward(name):
    from mamba_distributed_tpu.models import init_lm_params, lm_forward

    c, cfg = _cfg(name)
    m = c["model"]
    params = ref_init.init_params(ref_init.seed_key(2**31 + 9), m)
    theirs = jax.eval_shape(lambda k: init_lm_params(k, cfg), jax.random.PRNGKey(0))
    assert jax.tree.structure(theirs) == jax.tree.structure(params)
    assert all(a.shape == b.shape for a, b in
               zip(jax.tree.leaves(theirs), jax.tree.leaves(params)))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 4096, (2, 300)), jnp.int32)
    ids = jnp.pad(ids, ((0, 0), (0, 20)))  # the program wants whole SSD chunks
    want = ref_model.logits_fn(params, m, ids)
    got = lm_forward(params, cfg, ids).astype(jnp.float32)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-4
    # the control's precision moves the logits far more than the program does
    low = ref_model.logits_fn(params, m, ids, "fp8")
    assert float(jnp.max(jnp.abs(low - want))) > 100 * 2e-4


def test_ssd_direct_form_is_the_recurrence():
    rng = np.random.default_rng(1)
    b, t, h, p, n = 1, 70, 2, 4, 8
    x = jnp.asarray(rng.normal(size=(b, t, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(b, t, h)), jnp.float32)
    A = -jnp.asarray(rng.uniform(1, 4, size=(h,)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(b, t, 1, n)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(b, t, 1, n)), jnp.float32)
    D = jnp.asarray(rng.normal(size=(h,)), jnp.float32)
    got = np.asarray(ref_model.ssd(x, dt, A, B, C, D, q_block=32))
    state = np.zeros((h, p, n))
    for i in range(t):
        decay = np.exp(np.asarray(dt[0, i] * A))[:, None, None]
        state = decay * state + np.asarray(dt[0, i])[:, None, None] * np.einsum(
            "hp,n->hpn", np.asarray(x[0, i]), np.asarray(B[0, i, 0]))
        y = np.einsum("hpn,n->hp", state, np.asarray(C[0, i, 0])) \
            + np.asarray(D)[:, None] * np.asarray(x[0, i])
        assert np.allclose(got[0, i], y, atol=2e-5)


def test_reference_steps_match_optax_on_the_programs_loss():
    """Loss, gradient and AdamW of the reference against the program's loss
    under optax, in float32: the two must agree to rounding."""
    from mamba_distributed_tpu.config import get_preset
    from mamba_distributed_tpu.models import lm_loss
    from mamba_distributed_tpu.training.optimizer import make_optimizer

    c, cfg = _cfg("tiny-mamba2")
    m, t = c["model"], c["train"]
    tc = dataclasses.replace(get_preset("mamba2-tiny"), model=cfg)
    params = ref_init.init_params(ref_init.seed_key(5), m)
    rng = np.random.default_rng(2)
    batches = [(rng.integers(0, 4096, (2, 4, 256)).astype(np.int32),
                rng.integers(0, 4096, (2, 4, 256)).astype(np.int32))
               for _ in range(3)]
    ref = ref_train.first_steps(params, m, t, batches, row_block=4)
    opt = make_optimizer(tc)
    p, state, losses = params, opt.init(params), []
    for k, (x, y) in enumerate(batches):
        def loss_fn(q):
            return (lm_loss(q, cfg, jnp.asarray(x[0]), jnp.asarray(y[0]))
                    + lm_loss(q, cfg, jnp.asarray(x[1]), jnp.asarray(y[1]))) / 2
        loss, g = jax.value_and_grad(loss_fn)(p)
        losses.append(float(loss))
        upd, state = opt.update(g, state, p)
        if k == 0:
            mu = state[1][0].mu
            grad = ref_train.flat_norms(ref_train.leaf_norms(
                jax.tree.map(lambda a: a / (1 - t["adam_b1"]), mu)))
        p = jax.tree.map(jnp.add, p, upd)
    delta = ref_train.flat_norms(ref_train.leaf_norms(
        jax.tree.map(jnp.subtract, p, params)))
    got = ref_train.compare({"losses": losses, "grad": grad, "delta": delta}, ref)
    assert got["loss_gap"] < 1e-4 and got["grad_gap"] < 1e-3 and got["delta_gap"] < 2e-3


def test_a_leaf_with_no_gradient_is_left_out_of_the_change():
    ref = {"losses": [1.0], "grad": {"a": 1.0, "b": 1.0, "c": 1e-9},
           "delta": {"a": 1.0, "b": 1.0, "c": 1.0}}
    prog = {"losses": [1.0], "grad": dict(ref["grad"]),
            "delta": {"a": 1.0, "b": 1.1, "c": 5.0}}
    got = ref_train.compare(prog, ref)
    assert got["delta_gap"] == pytest.approx(0.1)
    assert got["_where"]["dead_leaves"] == 1
