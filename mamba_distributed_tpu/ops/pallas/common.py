"""Shared Pallas-kernel plumbing."""

from __future__ import annotations

import os

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve the ``interpret=None`` auto-default for a pallas_call.

    Auto picks the real Mosaic lowering on a TPU backend and the Pallas
    interpreter elsewhere, so CPU tests run the same kernel code.  The
    ``MDT_PALLAS_INTERPRET`` env var ("0"/"1") overrides auto-detection
    off a TPU — lowering tests set it to "0" to force the real Mosaic
    path through *composed* graphs (models, shard_map) that never see an
    ``interpret`` argument.  On a TPU backend asking for the interpreter
    is an error: it is the one way a kernel could run there without
    Mosaic ever compiling it.
    """
    if interpret is not None:
        return interpret
    env = _interpret_env()
    if env is not None:
        return env != "0"
    return not on_tpu()


def _interpret_env() -> str | None:
    """``MDT_PALLAS_INTERPRET``, refused when it asks for the
    interpreter on a TPU backend."""
    env = os.environ.get("MDT_PALLAS_INTERPRET")
    if env is not None and env != "0" and on_tpu():
        raise RuntimeError(
            f"MDT_PALLAS_INTERPRET={env} on a TPU backend would keep the "
            "Pallas kernels away from Mosaic; unset it (it is the CPU "
            "tests' lever)"
        )
    return env


def on_tpu() -> bool:
    """True when the default backend is a TPU."""
    return jax.default_backend() == "tpu"


def resolve_attn_impl(impl: str) -> str:
    """Resolve the ``attn_impl="auto"`` config default.

    On a TPU auto picks "pallas" (the flash and ragged paged kernels;
    ROADMAP S4 quotes the one train-throughput comparison there is); on
    CPU (tests, debugging) auto picks "xla" to avoid paying for the
    Pallas interpreter in composed graphs.

    ``MDT_ATTN_IMPL`` ("xla" | "pallas") overrides the probe directly and
    keeps the env contract single-purpose (ADVICE r4: overloading
    ``MDT_PALLAS_INTERPRET`` here was easy to misread).  Failing that,
    ``MDT_PALLAS_INTERPRET`` still steers auto for backwards
    compatibility — note the asymmetry: env=1 means "interpret Pallas
    kernels" for ``resolve_interpret`` but resolves *attention* to the
    XLA path, so ssm_impl="pallas" + attn_impl="auto" under env=1 runs
    interpreted SSM kernels next to XLA attention.  "0" (the chip-free
    ``jax.export`` TPU-lowering pattern) resolves auto to "pallas" so
    CPU-host exports targeting TPU bake in the kernels they'd get on
    hardware.
    """
    if impl != "auto":
        return impl
    env = os.environ.get("MDT_ATTN_IMPL")
    if env is not None:
        if env not in ("xla", "pallas"):
            raise ValueError(f"MDT_ATTN_IMPL must be xla|pallas, got {env!r}")
        return env
    env = _interpret_env()
    if env is not None:
        return "xla" if env != "0" else "pallas"
    return "pallas" if on_tpu() else "xla"
