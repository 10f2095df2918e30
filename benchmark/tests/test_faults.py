"""``correct`` has to come out false when the timed path is broken, and when
the lower-precision control stands in the program's place.

Each case drives the rest of a run (the harness's look for a chip skipped)
with one fault planted under it.  The limits here are the tiny cells' own,
set as the real ones are: between what sound runs read and what the control
reads at this size (see PERF.md for the real cells' readings).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import DATA


@pytest.fixture()
def tight_limits(monkeypatch):
    """The tiny cells' limits, tightened to this size's own readings: sound
    runs on the CPU under float32 products read loss 1e-4, grad 3e-3,
    delta 5e-3, logit 3e-3."""
    from benchmark import harness

    real = harness.load_json

    def load(path):
        d = real(path)
        if os.sep + "workloads" + os.sep in path:
            lim = d["limits"]
            for k, v in {"loss_gap": 2e-3, "grad_gap": 0.03, "delta_gap": 0.05,
                         "logit_gap": 0.03}.items():
                if k in lim:
                    lim[k] = v
        return d

    monkeypatch.setattr(harness, "load_json", load)


def _failed(line):
    return {k for k, c in line["compared"].items()
            if not (c["value"] is not None and c["value"] <= c["limit"])}


def test_sound_runs_pass_the_tight_limits(tiny_cell, tight_limits):
    assert tiny_cell("tiny-train")["correct"] is True
    assert tiny_cell("tiny-serve-open")["correct"] is True


def test_step_that_returns_its_state_unchanged(tiny_cell, tight_limits, monkeypatch):
    from mamba_distributed_tpu.training import trainer as trainer_mod

    real = trainer_mod.make_train_step

    def make(*a, **kw):
        step = real(*a, **kw)

        def frozen(params, opt_state, x, y):
            out = step(jax.tree.map(jnp.copy, params),
                       jax.tree.map(jnp.copy, opt_state), x, y)
            return (params, opt_state, *out[2:])
        return frozen

    monkeypatch.setattr(trainer_mod, "make_train_step", make)
    line = tiny_cell("tiny-train")
    assert line["correct"] is False
    assert {"grad_gap", "delta_gap"} <= _failed(line)
    assert line["compared"]["delta_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("keep,what", [(2, "half of the batch left out"),
                                       (4, "the exchange left out: one chip's rows of four")])
def test_part_of_the_batch_left_out(tiny_cell, tight_limits, monkeypatch, keep, what):
    """The mean taken over the rows that are left: the step sees the first
    1/keep of each micro-batch, repeated."""
    from mamba_distributed_tpu.training.trainer import Trainer

    real = Trainer._global_batch

    def fewer(self, accum, loader):
        x, y = real(self, accum, loader)
        n = x.shape[1] // keep
        rep = lambda a: jnp.tile(a[:, :n], (1, keep, 1))
        return rep(x), rep(y)

    monkeypatch.setattr(Trainer, "_global_batch", fewer)
    line = tiny_cell("tiny-train")
    assert line["correct"] is False, what
    assert "grad_gap" in _failed(line)


@pytest.mark.parametrize("cell", ["tiny-serve-open", "tiny-serve-closed"])
def test_a_token_altered_where_it_is_produced(tiny_cell, tight_limits, monkeypatch, cell):
    from mamba_distributed_tpu.serving import ServingEngine

    real = ServingEngine.step

    def step(self):
        events = real(self)
        for ev in events:
            if ev.index == 3:
                ev.token = (ev.token + 1234) % 4096
        return events

    monkeypatch.setattr(ServingEngine, "step", step)
    line = tiny_cell(cell)
    assert line["correct"] is False
    assert "logit_gap" in _failed(line)


def test_a_request_that_never_finishes(tiny_cell, monkeypatch):
    from mamba_distributed_tpu.serving import ServingEngine

    real = ServingEngine.step

    def step(self):
        return [ev for ev in real(self) if not (ev.done and ev.request_id % 5 == 0)]

    monkeypatch.setattr(ServingEngine, "step", step)
    line = tiny_cell("tiny-serve-open")
    assert line["correct"] is False and "incomplete" in _failed(line)
    assert line["failed"] > 0


@pytest.mark.parametrize("name,faults,must_fail", [
    ("tiny-train", ["half", "quarter", "frozen"], "grad_gap"),
    ("tiny-serve-open", [], "logit_gap"),
    ("tiny-serve-closed", [], "logit_gap"),
])
def test_the_control_and_the_planted_faults_are_judged_not_correct(
        tight_limits, name, faults, must_fail):
    """``control.py``'s own reading at a size a test run can hold: the program,
    then the reference in fp8 put in its place and the faults planted in the
    reference, each through the run's ``judge`` with the cell's limits.  The
    program reads ``correct`` true and every other line false."""
    from benchmark import control, harness

    cwd = os.getcwd()
    try:
        cell, devices, kind = harness.open_cell(
            name, os.path.join(DATA, "BENCHMARK.json"), DATA, require_tpu=False)
        rec = control.read_seed(cell, kind, devices, 11, 2.5, "fp8", faults)
    finally:
        os.chdir(cwd)
    assert rec["program"]["correct"] is True
    others = {k: v for k, v in rec.items() if k.startswith(("control_", "fault_"))}
    assert len(others) == 1 + len(faults)
    for k, v in others.items():
        assert v["correct"] is False, k
    assert must_fail in rec["control_fp8"]["failed"]
    if faults:
        assert rec["fault_state_unchanged"]["compared"]["delta_gap"]["value"] \
            == pytest.approx(1.0)
