"""The harness end to end at a tiny size, once for each kind of workload:
the result object against the contract's keys and character rules."""

import json
import os
import re
import subprocess
import sys

import pytest

from conftest import CHECKOUT, DATA

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _check_line(line, manifest, cell, trace):
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "compared"  # each number beside its limit, last
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    group = manifest["per_layer"] if trace else manifest["end_to_end"]
    mine = {e["name"]: e for e in group if cell in e.get("workloads", [cell])}
    for name, v in line["metrics"].items():
        assert NAME.match(name) and UNIT.match(v["unit"])
        assert name in mine and v["unit"] == mine[name]["unit"]
        assert isinstance(v["value"], float) and v["value"] == v["value"]
    if not trace:
        assert set(line["metrics"]) == set(mine)
        assert all(v["value"] > 0 for v in line["metrics"].values())
    for c in line["compared"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
    json.dumps(line)


@pytest.mark.parametrize("cell", ["tiny-train", "tiny-serve-open",
                                  "tiny-serve-closed", "tiny-train-dp4"])
def test_kind_end_to_end(tiny_cell, cell):
    manifest = json.load(open(os.path.join(DATA, "BENCHMARK.json")))
    line = tiny_cell(cell, seed=2**31 + 17)
    _check_line(line, manifest, cell, trace=False)
    assert line["compared"]["window_compiles"]["value"] == 0
    assert line["device"]["count"] == (4 if cell.endswith("dp4") else 1)


def test_per_layer_line_prints_no_device_metric_off_a_tpu(tiny_cell):
    """--trace 1 on the CPU: host-span metrics are read; shares of a peak or
    of a roofline find no TPU and are left out, never printed as 0."""
    manifest = json.load(open(os.path.join(DATA, "BENCHMARK.json")))
    line = tiny_cell("tiny-train", seed=3, trace=True)
    _check_line(line, manifest, "tiny-train", trace=True)
    assert "data_load_share.train" in line["metrics"]
    assert "step_mfu.train" not in line["metrics"]


def test_command_line_refuses_without_a_tpu():
    """The command itself: no accelerator, exit code other than 0, no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(CHECKOUT, "benchmark", "run.py"),
         "--workload", "train-mamba2-280m-1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert not any(l.startswith("{") for l in r.stdout.splitlines())


def test_unknown_cell_is_refused(tiny_cell):
    from benchmark.harness import Refused

    with pytest.raises(Refused):
        tiny_cell("no-such-cell")


def test_compile_watch_sees_an_eager_call_compile():
    import time

    import jax.numpy as jnp

    from benchmark import harness

    watch = harness.CompileWatch()
    t0 = time.perf_counter()
    jnp.pad(jnp.ones((1, 37)), ((0, 0), (5, 0))).block_until_ready()
    t1 = time.perf_counter()
    assert watch.report(t0, t1, {"tick": 0}) >= 1
    jnp.pad(jnp.ones((1, 37)), ((0, 0), (5, 0))).block_until_ready()  # cached
    assert watch.within(t1, time.perf_counter()) == []
    watch.close()


def test_breakdown_names_are_short():
    from benchmark.harness import short_ops

    long = ('%closed_call.27 = bf16[16,4,8,64]{3,2,1,0:T(8,128)(2,1)S(1)} '
            'custom-call(s32[16,128]{1,0:T(8,128)} %x), custom_call_target='
            '"tpu_custom_call", operand_layout_constraints={}')
    got = short_ops([[long, 0.5], ["%fusion.1 = f32[8]{0} fusion(f32[8] %a), "
                                   "kind=kLoop, calls=%c", 0.25]] * 7)
    assert got == [["closed_call.27 bf16[16,4,8,64] tpu_custom_call", 3.5],
                   ["fusion.1 f32[8] kLoop", 1.75]]
