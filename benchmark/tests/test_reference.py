"""benchmark/reference against the program at a tiny size, full float32, and
the default module's weights and layer-at-a-time walk against the whole tree."""

import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference
from benchmark.reference import mamba2 as ref_mamba2
from benchmark.reference import model as ref_model
from benchmark.reference import train as ref_train
from conftest import DATA

NAMES = ["tiny-mamba2", "tiny-hybrid"]


def _cfg(name):
    from mamba_distributed_tpu.config import get_preset

    c = json.load(open(os.path.join(DATA, "configs", name + ".json")))
    cfg = dataclasses.replace(get_preset(c["preset"]).model,
                              compute_dtype="float32", **c.get("serving", {}))
    return c, cfg


def _leaves(tree):
    return {"/".join(str(getattr(k, "key", k)) for k in path): np.asarray(leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.mark.parametrize("name", NAMES)
def test_float32_weights_are_the_parent_commits_bit_for_bit(name):
    """``data/weights_digest.json`` holds the sha256 of every leaf that
    ``reference/init.py:init_params`` gave at commit 27a723b (one ``jax.vmap``
    over the layers' keys) under one jitted call; the draw a layer at a time
    has to give the same bits."""
    want = json.load(open(os.path.join(DATA, "weights_digest.json")))[name]
    m = _cfg(name)[0]["model"]
    tree = jax.jit(lambda k: ref_mamba2.init_params(k, m, "float32"))(
        reference.seed_key(want["seed"]))
    got = {n: [str(a.dtype), list(a.shape), hashlib.sha256(a.tobytes()).hexdigest()]
           for n, a in _leaves(tree).items()}
    assert got == want["leaves"]


@pytest.mark.parametrize("name", NAMES)
def test_bfloat16_weights_are_the_float32_draw_rounded_once(name):
    m = _cfg(name)[0]["model"]
    key = reference.seed_key(2**31 + 21)
    full = _leaves(jax.jit(lambda k: ref_mamba2.init_params(k, m, "float32"))(key))
    half = _leaves(jax.jit(lambda k: ref_mamba2.init_params(k, m, "bfloat16"))(key))
    assert set(full) == set(half)
    for n, a in full.items():
        assert half[n].dtype == jnp.bfloat16
        assert np.array_equal(half[n], a.astype(jnp.bfloat16)), n


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", NAMES)
def test_the_walk_a_layer_at_a_time_gives_the_whole_trees_logits(name, dtype):
    """``served_logits`` draws each layer where it reaches it and never holds
    the tree: exactly the logits of ``logits_fn`` on the whole tree raised to
    float32, and at a few positions exactly what the parent commit's
    ``served_logits`` computed (``hidden_states`` on the tree, the head on
    those rows alone), in the reference's precision and in the control's."""
    m = _cfg(name)[0]["model"]
    key = reference.seed_key(2**31 + 33)
    ids = jnp.asarray(np.random.default_rng(3).integers(0, 4096, (1, 200)), jnp.int32)
    few = jnp.asarray([0, 7, 150, 199], jnp.int32)
    tree = jax.tree.map(lambda a: a.astype(jnp.float32),
                        jax.jit(lambda k: ref_mamba2.init_params(k, m, dtype))(key))

    def head_on_rows(params, precision):
        h = ref_model.hidden_states(params, m, ids, precision)
        h = ref_model.rms_norm(h[0, few], params["norm_f"]["weight"], m["norm_eps"])
        return ref_model.mm(h, params["embedding"].T, precision)

    for precision in ("f32", "fp8"):
        whole = jax.jit(lambda p: ref_model.logits_fn(p, m, ids, precision))(tree)
        walked = ref_mamba2.served_logits(key, m, dtype, ids, jnp.arange(200), precision)
        assert np.array_equal(np.asarray(whole)[0], np.asarray(walked)), precision
        rows = jax.jit(lambda p: head_on_rows(p, precision))(tree)
        walked = ref_mamba2.served_logits(key, m, dtype, ids, few, precision)
        assert np.array_equal(np.asarray(rows), np.asarray(walked)), precision


@pytest.mark.parametrize("name", NAMES)
def test_forward_matches_lm_forward(name):
    from mamba_distributed_tpu.models import init_lm_params, lm_forward

    c, cfg = _cfg(name)
    m = c["model"]
    params = ref_mamba2.init_params(reference.seed_key(2**31 + 9), m, "float32")
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
    params = ref_mamba2.init_params(reference.seed_key(5), m, "float32")
    rng = np.random.default_rng(2)
    batches = [(rng.integers(0, 4096, (2, 4, 256)).astype(np.int32),
                rng.integers(0, 4096, (2, 4, 256)).astype(np.int32))
               for _ in range(3)]
    ref = ref_train.first_steps(ref_mamba2, params, m, t, batches, row_block=4)
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
                jax.tree.map(lambda a: a / (1 - t["adam_b1"]), mu),
                ref_mamba2.STACKED))
        p = jax.tree.map(jnp.add, p, upd)
    delta = ref_train.flat_norms(ref_train.leaf_norms(
        jax.tree.map(jnp.subtract, p, params), ref_mamba2.STACKED))
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
