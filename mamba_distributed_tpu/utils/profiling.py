"""Profiling: jax.profiler trace capture + simple step timing.

The reference's only instrumentation is wall-clock around the step with a
device synchronize (/root/reference/train.py:129,228-238).  Here:
  * ``trace(dir)`` — context manager capturing a TensorBoard-viewable
    XLA trace (kernel timeline, HBM traffic) via ``jax.profiler``;
  * ``StepTimer`` — host-side step timing with a forced device sync
    (transfer of a scalar), the moral equivalent of cuda.synchronize.
"""

from __future__ import annotations

import contextlib
import time
import warnings

import jax


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture a profiler trace for the enclosed steps.

    View with TensorBoard's profile plugin pointed at ``log_dir``.
    """
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class StepTimer:
    """Wall-clock timing with an explicit sync on a device scalar
    (fetching its value waits for the work that produced it)."""

    def __init__(self):
        self._t0 = None

    def start(self) -> None:
        self._t0 = time.time()

    def stop(self, sync_scalar=None) -> float:
        if sync_scalar is not None:
            float(jax.device_get(sync_scalar))
        if self._t0 is None:
            # a timing bug must not kill the run it is measuring
            warnings.warn(
                "StepTimer.stop() called without start(); returning 0.0",
                RuntimeWarning, stacklevel=2,
            )
            return 0.0
        return time.time() - self._t0
