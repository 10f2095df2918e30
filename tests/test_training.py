"""Training-subsystem tests: LR schedule, decay mask, grad accum, end-to-end.

The schedule/optimizer values are pinned to the reference's constants
(/root/reference/train.py:89-110, model.py:126-148).
"""

import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mamba_distributed_tpu.config import TrainConfig
from mamba_distributed_tpu.training.optimizer import decay_mask, lr_schedule
from tests.test_parallel import losses_of, make_cfg


def ref_get_lr(it, max_lr=6e-4, min_lr=6e-5, warmup=715, max_steps=19073):
    """The reference get_lr (train.py:97-110), re-stated for the test."""
    if it < warmup:
        return max_lr * (it + 1) / warmup
    if it > max_steps:
        return min_lr
    decay_ratio = (it - warmup) / (max_steps - warmup)
    coeff = 0.5 * (1.0 + math.cos(math.pi * decay_ratio))
    return min_lr + coeff * (max_lr - min_lr)


def test_lr_schedule_matches_reference():
    cfg = TrainConfig()
    sched = lr_schedule(cfg)
    for it in [0, 1, 100, 714, 715, 716, 5000, 10000, 19072, 19073]:
        np.testing.assert_allclose(
            float(sched(it)), ref_get_lr(it), rtol=1e-6, err_msg=str(it)
        )


def test_decay_mask_dim_rule():
    params = {
        "w": jnp.ones((4, 4)),       # decayed
        "emb": jnp.ones((8, 2)),     # decayed
        "b": jnp.ones((4,)),         # not
        "scalar": jnp.ones(()),      # not
    }
    mask = decay_mask(params)
    assert mask["w"] and mask["emb"]
    assert not mask["b"] and not mask["scalar"]


def test_decay_mask_on_real_stacked_tree():
    """The scan-over-layers leading axis must not count toward the dim>=2
    rule: per-layer 1D params (norms, biases, dt/A/D) never decay."""
    from mamba_distributed_tpu.config import ModelConfig
    from mamba_distributed_tpu.models import init_lm_params
    from tests.test_parallel import TINY_MODEL

    cfg = ModelConfig(**TINY_MODEL)
    params = jax.eval_shape(
        lambda k: init_lm_params(k, cfg), jax.random.PRNGKey(0)
    )
    mask = decay_mask(params)
    blocks = mask["blocks"]
    assert not blocks["norm"]["weight"]
    assert not blocks["mixer"]["dt_bias"]
    assert not blocks["mixer"]["A_log"]
    assert not blocks["mixer"]["D"]
    assert not blocks["mixer"]["conv"]["bias"]
    assert blocks["mixer"]["in_proj"]["kernel"]
    assert blocks["mixer"]["out_proj"]["kernel"]
    assert blocks["mixer"]["conv"]["kernel"]
    assert mask["embedding"]
    assert not mask["norm_f"]["weight"]


@pytest.mark.slow
def test_grad_accum_equals_big_batch(tmp_path):
    """accum x B == one 2B batch: same loss and same updated params."""
    l1, t1 = losses_of(tmp_path / "a", steps=2, micro=8, accum=2)
    l2, t2 = losses_of(tmp_path / "b", steps=2, micro=16, accum=1)
    np.testing.assert_allclose(l1, l2, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(t1.params), jax.tree.leaves(t2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.slow
def test_loss_decreases_end_to_end(tmp_path):
    losses, _ = losses_of(tmp_path, steps=8)
    assert losses[-1] < losses[0] - 0.05, losses


@pytest.mark.slow
def test_bf16_compute_loss_impact(tmp_path):
    """End-to-end loss impact of the bf16 compute policy (round-1 review
    asked for this to be quantified, not just per-op tolerances): the same
    8-step trajectory in bf16 compute vs fp32 compute must agree to well
    under the loss *movement* over those steps."""
    lf32, _ = losses_of(tmp_path / "f32", steps=8)
    lbf16, _ = losses_of(
        tmp_path / "bf16", steps=8, model_over={"compute_dtype": "bfloat16"}
    )
    lf32, lbf16 = np.asarray(lf32), np.asarray(lbf16)
    movement = lf32[0] - lf32[-1]
    assert movement > 0.05  # the run actually learns
    # bf16 rounding shifts each step's loss by far less than what a step of
    # training changes it — i.e. the precision policy doesn't alter the
    # curve at the scale the reference log is compared at
    np.testing.assert_allclose(lbf16, lf32, atol=0.25 * float(movement))


def test_log_format_matches_reference(tmp_path):
    from mamba_distributed_tpu.training import Trainer

    t = Trainer(make_cfg(tmp_path), verbose=True)
    t.run(max_steps=2)
    log = open(os.path.join(str(tmp_path), "log", "log.txt")).read().splitlines()
    # reference format: "{step} train {loss:.6f}" / "{step} val {loss:.4f}"
    assert any(
        len(p) == 3 and p[1] == "train" and len(p[2].split(".")[1]) == 6
        for p in (ln.split() for ln in log)
    )
    assert any(
        len(p) == 3 and p[1] == "val" and len(p[2].split(".")[1]) == 4
        for p in (ln.split() for ln in log)
    )


def test_structured_metrics_jsonl(tmp_path):
    """Alongside the reference-format log.txt, metrics.jsonl carries the
    structured per-step record (SURVEY.md §5)."""
    import json

    from mamba_distributed_tpu.training import Trainer

    t = Trainer(make_cfg(tmp_path), verbose=True)
    t.run(max_steps=2)
    lines = [
        json.loads(ln)
        for ln in open(os.path.join(str(tmp_path), "log", "metrics.jsonl"))
    ]
    train = [r for r in lines if r["kind"] == "train"]
    val = [r for r in lines if r["kind"] == "val"]
    assert len(train) == 2 and len(val) >= 1
    for r in train:
        assert {"step", "loss", "lr", "grad_norm", "step_ms",
                "tokens_per_sec"} <= set(r)
        # MFU is a statement about a TPU; on the CPU none is logged
        assert "mfu" not in r and "mfu_hw" not in r


def test_in_loop_sampling(tmp_path, capsys):
    """Reference-style in-training sampling (train.py:166-199): 4 rows of
    prompt + 32 new tokens, decoded via the injected decode_fn."""
    from mamba_distributed_tpu.training import Trainer

    t = Trainer(
        make_cfg(tmp_path), verbose=True,
        sample_prompt_ids=[1, 2, 3],
        decode_fn=lambda ids: " ".join(map(str, ids)),
    )
    out = t.sample(num_return=4, max_new_tokens=8)
    assert out.shape == (4, 11)
    captured = capsys.readouterr().out
    assert captured.count("sample: ") == 4


@pytest.mark.slow
def test_async_checkpoint_overlap(tmp_path):
    """Back-to-back async saves + restore of the latest committed step:
    the write overlaps training and restore never reads a partial write."""
    from mamba_distributed_tpu.training import Trainer

    ckpt = str(tmp_path / "ckpt")
    t = Trainer(make_cfg(tmp_path / "w"), verbose=False)
    t.run(max_steps=1)
    t.save_checkpoint(ckpt)
    t.run(max_steps=2)
    t.save_checkpoint(ckpt)  # second save while the first may be in flight
    t.run(max_steps=3)
    t.finish()

    t2 = Trainer(make_cfg(tmp_path / "w"), verbose=False)
    t2.restore_checkpoint(ckpt)
    assert t2.step == 2  # latest committed step


@pytest.mark.slow
def test_checkpoint_exact_resume(tmp_path):
    """Kill-and-resume reproduces the exact loss trajectory (VERDICT item 7)."""
    from mamba_distributed_tpu.training import Trainer

    ckpt = str(tmp_path / "ckpt")
    t1 = Trainer(make_cfg(tmp_path / "w1"), verbose=True)
    t1.run(max_steps=3)
    t1.save_checkpoint(ckpt)
    t1.run(max_steps=6)
    expect = [
        float(ln.split()[2])
        for ln in open(os.path.join(str(tmp_path / "w1"), "log", "log.txt"))
        if " train " in ln
    ][3:]

    t2 = Trainer(make_cfg(tmp_path / "w1"), verbose=False)
    t2.restore_checkpoint(ckpt)
    assert t2.step == 3
    got = []
    for _ in range(3):
        x, y = t2._global_batch(t2.cfg.grad_accum_steps, t2.train_loader)
        t2.params, t2.opt_state, loss, _ = t2.train_step(t2.params, t2.opt_state, x, y)
        got.append(float(loss))
    np.testing.assert_allclose(expect, got, rtol=1e-6)


@pytest.mark.slow
def test_cli_sampling_wiring(tmp_path, capsys):
    """The root train.py CLI threads --sample-prompt-ids through to
    Trainer.sample (VERDICT r2: sampling must be a shipped feature, not a
    library one; reference behavior at /root/reference/train.py:166-199)."""
    import train as train_cli

    import dataclasses

    from mamba_distributed_tpu.training import Trainer

    ids, decode = train_cli.resolve_sampling(
        type("A", (), {"sample_prompt_ids": "5,7,11", "sample_prompt": None})()
    )
    assert ids == [5, 7, 11] and decode is None

    cfg = dataclasses.replace(make_cfg(tmp_path), sample_every=2, max_steps=3)
    tr = Trainer(cfg, sample_prompt_ids=ids)
    tr.run(max_steps=3)
    out = capsys.readouterr().out
    assert "sample:" in out, out


def test_cli_auto_restart_recovers(tmp_path, capsys, monkeypatch):
    """--auto-restart: a mid-run crash rebuilds the trainer from the
    latest checkpoint and the run completes (restart-based failure
    recovery; the reference's torchrun job just dies)."""
    import dataclasses
    import sys

    import train as train_cli
    from mamba_distributed_tpu.training import Trainer

    cfg = make_cfg(tmp_path)
    monkeypatch.setattr(
        train_cli, "build_config",
        lambda args: dataclasses.replace(cfg, checkpoint_every=2, max_steps=5),
    )

    # crash exactly once, at step 3 of the first trainer
    orig_run = Trainer.run
    state = {"crashed": False}

    def crashing_run(self, max_steps=None, checkpoint_dir=None):
        if not state["crashed"]:
            orig = self.train_step

            def stepper(params, opt, x, y):
                if self.step >= 3:
                    state["crashed"] = True
                    raise RuntimeError("injected chip failure")
                return orig(params, opt, x, y)

            self.train_step = stepper
        return orig_run(self, max_steps=max_steps, checkpoint_dir=checkpoint_dir)

    monkeypatch.setattr(Trainer, "run", crashing_run)
    ckpt = str(tmp_path / "ckpt")
    monkeypatch.setattr(sys, "argv", [
        "train.py", "--checkpoint-dir", ckpt, "--auto-restart", "1",
    ])
    train_cli.main()
    out = capsys.readouterr().out
    assert "restart 1/1" in out, out
    assert "resumed from step 2" in out, out  # latest checkpoint (every 2)
    # the run completed after recovery
    log = (tmp_path / "log" / "log.txt").read_text()
    assert "4 train" in log
