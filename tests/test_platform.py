"""Runtime set-up contracts: compile-cache placement, no MFU off a TPU,
no interpreted kernels on one, and the chip tools' refusal without a chip."""

import json
import os
import subprocess
import sys
import types

import jax
import pytest

from mamba_distributed_tpu.utils import platform

pytestmark = pytest.mark.fast

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_config():
    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
    )}
    yield
    for k, v in keep.items():
        jax.config.update(k, v)


def test_compile_cache_left_alone_when_placed_from_outside(
        monkeypatch, tmp_path, restore_cache_config):
    """JAX_COMPILATION_CACHE_DIR set: the code sets no directory at all
    (JAX reads the variable itself) and reports the outside one."""
    jax.config.update("jax_compilation_cache_dir", "/sentinel/untouched")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert platform.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == "/sentinel/untouched"


def test_compile_cache_defaults_to_one_fixed_checkout_dir(
        monkeypatch, restore_cache_config):
    """Unset: the same directory inside the checkout on every call — the
    path is part of the cache key, so it may not move."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = platform.configure_compile_cache()
    assert platform.configure_compile_cache() == first
    assert first == os.path.join(REPO, ".cache", "jax")
    assert jax.config.jax_compilation_cache_dir == first


def test_peak_flops_has_no_default():
    from mamba_distributed_tpu.utils.flops import peak_flops_per_chip

    v5e = types.SimpleNamespace(device_kind="TPU v5 lite")
    assert peak_flops_per_chip(v5e) == 197e12
    with pytest.raises(ValueError, match="device_kind"):
        peak_flops_per_chip(jax.devices()[0])  # "cpu": not in the table


def test_flops_conventions():
    """mfu_model's FLOPs basis must be strictly below the hardware
    convention for mamba2 (chunked overhead dropped) and identical for
    mamba1 (already the recurrence)."""
    from mamba_distributed_tpu.config import get_preset

    m2 = get_preset("mamba2-280m").model
    from mamba_distributed_tpu.utils.flops import flops_per_token

    hw = flops_per_token(m2, 1024, convention="hardware")
    model = flops_per_token(m2, 1024, convention="model")
    assert model < hw
    m1 = get_preset("mamba1-280m").model
    assert flops_per_token(m1, 1024, convention="hardware") == flops_per_token(
        m1, 1024, convention="model"
    )
    with pytest.raises(ValueError, match="convention"):
        flops_per_token(m2, 1024, convention="6nd")


def test_no_mfu_off_a_tpu(tmp_path, capsys):
    """Trainer and ServingEngine construct on the CPU, say what they run
    on, and compute no MFU against some TPU's peak."""
    import dataclasses

    from mamba_distributed_tpu.config import get_preset
    from mamba_distributed_tpu.models import init_lm_params
    from mamba_distributed_tpu.serving import ServingEngine
    from mamba_distributed_tpu.training import Trainer

    cfg = get_preset("mamba2-tiny")
    cfg = dataclasses.replace(
        cfg, log_dir=str(tmp_path / "log"),
        data=dataclasses.replace(cfg.data, data_dir=str(tmp_path / "data"),
                                 synthetic_tokens_per_shard=16384),
    )
    trainer = Trainer(cfg)
    assert trainer._peak is None
    engine = ServingEngine(
        init_lm_params(jax.random.PRNGKey(0), cfg.model), cfg.model,
        capacity=2,
    )
    assert engine.metrics.summary()["goodput"]["serving_mfu"] is None
    out = capsys.readouterr()
    assert "trainer: platform cpu | device_kind cpu | devices 1 of 8" in out.out
    assert "serving engine: platform cpu" in out.err


def test_interpret_env_is_an_error_on_a_tpu_backend(monkeypatch):
    """MDT_PALLAS_INTERPRET=1 is the CPU tests' lever; on a TPU backend
    it is the one way a kernel could dodge Mosaic, so it raises."""
    from mamba_distributed_tpu.ops.pallas import common

    monkeypatch.setenv("MDT_PALLAS_INTERPRET", "1")
    assert common.resolve_interpret(None) is True  # CPU: honoured
    assert common.resolve_attn_impl("auto") == "xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="MDT_PALLAS_INTERPRET"):
        common.resolve_interpret(None)
    with pytest.raises(RuntimeError, match="MDT_PALLAS_INTERPRET"):
        common.resolve_attn_impl("auto")
    monkeypatch.delenv("MDT_PALLAS_INTERPRET")
    assert common.resolve_interpret(None) is False
    assert common.resolve_attn_impl("auto") == "pallas"


@pytest.mark.parametrize("tool", ["chip_smoke.py", "scripts/tpu_smoke.py"])
def test_chip_smoke_refuses_without_a_chip(tool):
    """No accelerator: non-zero exit, one line naming the platform, and
    no result on stdout (``platform.init_backend`` owns the refusal)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MDT_PALLAS_INTERPRET", None)
    env.pop("MDT_ATTN_IMPL", None)
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, tool)],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert p.returncode != 0
    assert p.stdout == ""
    assert "platform is 'cpu'" in p.stderr.strip().splitlines()[-1]


def test_chip_smoke_verdict_line_has_the_contract_keys_only():
    """The driver parses the last stdout line and refuses any other key."""
    import chip_smoke

    last = json.loads(chip_smoke.verdict_line(True, jax.devices()))
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert last["device"] == {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()),
    }
