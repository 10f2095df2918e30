"""A configuration's reference module is found by a key of its file, and held
to the contract of ``benchmark/reference/__init__.py``."""

import collections
import sys
import types

import pytest

from benchmark import harness, reference
from benchmark.reference import mamba2


def _double(monkeypatch, name, leave_out=()):
    """A second reference module, put where the lookup finds it: the default
    one's duties behind counters, less those of ``leave_out``."""
    calls = collections.Counter()
    mod = types.ModuleType("benchmark.reference." + name)

    def counted(fn):
        def call(*a, **kw):
            calls[fn.__name__] += 1
            return fn(*a, **kw)
        return call

    for names in reference.DUTIES.values():
        for n in names:
            if n not in leave_out:
                have = getattr(mamba2, n)
                setattr(mod, n, counted(have) if callable(have) else have)
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    return mod, calls


def test_a_configuration_that_names_none_gets_the_default():
    assert reference.of({"name": "x"}) is mamba2
    assert reference.of({"reference": "mamba2"}) is mamba2


@pytest.mark.parametrize("cell,used", [
    ("tiny-serve-open-double", {"init_params", "served_logits",
                                "forward_flops_per_token"}),
    ("tiny-train-double", {"init_params", "loss_sum", "train_flops_per_token"}),
])
def test_a_named_reference_serves_the_whole_run(tiny_cell, monkeypatch, cell, used):
    """``configs/tiny-mamba2-double.json`` names ``double_for_test``, which is
    no file under ``benchmark/reference/``: the kinds reach every duty they
    need through the lookup, and the window watches the modules the file's
    ``trace_count_modules`` names."""
    mod, calls = _double(monkeypatch, "double_for_test")
    watched = []
    real = harness.trace_counts
    monkeypatch.setattr(harness, "trace_counts",
                        lambda modules: watched.append(list(modules)) or real(modules))
    line = tiny_cell(cell)
    assert line["correct"] is True and line["attempted"] > 0
    assert used <= {n for n, c in calls.items() if c > 0}
    assert watched and all(w == [
        "mamba_distributed_tpu.serving.engine", "mamba_distributed_tpu.serving.prefill",
        "mamba_distributed_tpu.training.train_step"] for w in watched)
    assert line["compared"]["window_compiles"]["value"] == 0


@pytest.mark.parametrize("duty", sorted(reference.DUTIES))
def test_a_reference_that_lacks_a_duty_is_refused_by_name(monkeypatch, duty):
    lacking = reference.DUTIES[duty][-1]
    _double(monkeypatch, "lacking_for_test", leave_out=(lacking,))
    with pytest.raises(harness.Refused) as e:
        reference.of({"reference": "lacking_for_test"})
    assert lacking in str(e.value) and repr(duty) in str(e.value)


def test_a_reference_with_no_module_is_refused():
    with pytest.raises(harness.Refused, match="no benchmark/reference/no_such.py"):
        reference.of({"reference": "no_such"})
