"""bench.py harness contracts (no device work — config/error paths only)."""

import os
import sys

import pytest

pytestmark = pytest.mark.fast  # sub-2-min inner-loop tier

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402


def test_time_config_reports_errors_instead_of_raising():
    """Sweeps must survive a bad configuration (e.g. OOM on hardware);
    the error comes back as data."""
    r = bench.time_config({"ssm_impl": "bogus"}, iters=1)
    assert "error" in r and "ValueError" in r["error"]
    assert r["ssm_impl"] == "bogus"  # spec echoed for attribution


def test_main_without_a_tpu_exits_nonzero_and_prints_no_number(capsys):
    """No TPU is a non-zero exit and nothing on stdout — never a number
    from somewhere else (the suite runs with JAX_PLATFORMS=cpu)."""
    with pytest.raises(SystemExit) as ei:
        bench.main()
    assert ei.value.code not in (0, None)
    assert "platform is 'cpu'" in str(ei.value.code)
    assert capsys.readouterr().out == ""


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


def test_main_rejects_bad_iters_before_the_backend(monkeypatch, capsys):
    """A malformed BENCH_ITERS is reported before any backend work, with
    a non-zero exit and nothing on stdout."""
    def boom():
        raise AssertionError("backend initialized before env validation")

    monkeypatch.setattr(bench, "init_backend", boom)
    monkeypatch.setenv("BENCH_ITERS", "abc")
    with pytest.raises(SystemExit, match="bad_env_spec"):
        bench.main()
    assert capsys.readouterr().out == ""


def test_env_spec_rejects_bad_remat(monkeypatch):
    monkeypatch.setenv("BENCH_REMAT", "yes")
    with pytest.raises(SystemExit, match="BENCH_REMAT"):
        bench._env_spec()


def test_env_spec_defaults_are_baseline_recipe(monkeypatch):
    for var in ("BENCH_B", "BENCH_T", "BENCH_PRESET", "BENCH_SSM_IMPL",
                "BENCH_REMAT", "BENCH_REMAT_POLICY"):
        monkeypatch.delenv(var, raising=False)
    spec = bench._env_spec()
    assert spec["preset"] == bench.BASELINE_PRESET
    assert spec["T"] == bench.BASELINE_T


def test_sweep_default_configs_are_constructible():
    """Every spec in the default sweep matrix must build a valid config —
    a typo'd key or value should fail here, not on the chip."""
    import dataclasses

    sys.path.insert(0, os.path.join(REPO, "scripts"))
    from sweep_bench import DEFAULT_CONFIGS
    from mamba_distributed_tpu.config import get_preset

    known = {"preset", "B", "T", *bench.MODEL_SPEC_KEYS}
    for spec in DEFAULT_CONFIGS:
        assert set(spec) <= known, spec
        B = spec.get("B", bench.DEFAULT_B)
        T = spec.get("T", bench.DEFAULT_T)
        cfg = get_preset(spec.get("preset", bench.DEFAULT_PRESET),
                         micro_batch_size=B, seq_len=T,
                         total_batch_size=B * T)
        over = {k: spec[k] for k in bench.MODEL_SPEC_KEYS if k in spec}
        if over:
            # ModelConfig.__post_init__ validates the values
            dataclasses.replace(cfg.model, **over)
