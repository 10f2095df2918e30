"""XLA compile watchdog: count and time every backend compile.

The repo's pow2 bucketing (prompt buckets, the decode tick's lane
ladder, spec lanes) exists to BOUND recompiles — which makes silent
recompile thrash the production failure mode nothing watched until
now: a config that defeats the bucketing (or an occupancy pattern that
oscillates across a pow2 boundary) turns every tick into a multi-ms
XLA compile and the only symptom is a mysteriously bad ITL histogram.

``CompileWatchdog`` hooks ``jax.monitoring`` (the
``/jax/.../backend_compile_duration`` event fires once per XLA backend
compile, with its wall duration) and keeps:

  * process-lifetime totals (``compiles`` / ``compile_ms``) — exposed
    as counters on ``GET /metrics`` and in ``summary()``;
  * per-drain window deltas — the engine drains them each tick and
    stamps ``compiles``/``compile_ms`` on the ``serving_tick`` record
    (None-gated: no watchdog, no stamp — the byte-stability contract
    every optional plane in this repo keeps);
  * a tumbling thrash window: more than ``thrash_threshold`` compiles
    inside one ``thrash_window_s`` raises ONE ``compile_thrash`` event
    record through the tracer (the ``slo_breach`` discipline — once
    per window, never a per-compile flood).

Strictly host-side: the listener runs on the thread that triggered
the compile, after the compile.
"""

from __future__ import annotations

import threading
import time

import jax.monitoring

from mamba_distributed_tpu.obs.tracer import NULL_TRACER

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileWatchdog:
    """Counts/times XLA backend compiles; raises on compile thrash.

    Args:
      thrash_threshold: compiles allowed per window before the
        ``compile_thrash`` event fires; 0 disables thrash detection
        (counting still works).
      thrash_window_s: tumbling window length in seconds.
      tracer: where the ``compile_thrash`` event record lands.
      _clock: injectable monotonic clock (tests).
    """

    def __init__(self, *, thrash_threshold: int = 0,
                 thrash_window_s: float = 60.0, tracer=NULL_TRACER,
                 _clock=time.monotonic):
        if thrash_threshold < 0:
            raise ValueError(
                f"thrash_threshold must be >= 0 (0 disables), got "
                f"{thrash_threshold}"
            )
        if thrash_window_s <= 0:
            raise ValueError(
                f"thrash_window_s must be > 0, got {thrash_window_s}"
            )
        self.thrash_threshold = thrash_threshold
        self.thrash_window_s = thrash_window_s
        self.tracer = tracer
        self._clock = _clock
        self._lock = threading.Lock()
        # process-lifetime totals
        self.compiles = 0
        self.compile_ms = 0.0
        # per-drain window (engine tick stamps)
        self._win_compiles = 0
        self._win_ms = 0.0
        # tumbling thrash window
        self._thrash_t0 = _clock()
        self._thrash_count = 0
        self._thrash_fired = False
        self.thrash_events = 0
        self._listener = None

    # ---------------------------------------------------------- install

    def install(self) -> None:
        """Register the ``jax.monitoring`` duration listener.
        Idempotent."""
        if self._listener is not None:
            return

        def listener(event, duration, **kwargs):
            if event == _COMPILE_EVENT:
                self.on_compile(duration)

        jax.monitoring.register_event_duration_secs_listener(listener)
        self._listener = listener

    def uninstall(self) -> None:
        """Deregister the listener (tests install/uninstall repeatedly
        and must not stack listeners)."""
        if self._listener is None:
            return
        jax.monitoring.unregister_event_duration_listener(self._listener)
        self._listener = None

    # ------------------------------------------------------------- feed

    def on_compile(self, duration_s: float) -> None:
        """One backend compile of ``duration_s`` seconds."""
        now = self._clock()
        fire_attrs = None
        with self._lock:
            ms = float(duration_s) * 1000.0
            self.compiles += 1
            self.compile_ms += ms
            self._win_compiles += 1
            self._win_ms += ms
            if self.thrash_threshold > 0:
                if now - self._thrash_t0 >= self.thrash_window_s:
                    # tumbling window rollover: re-arm
                    self._thrash_t0 = now
                    self._thrash_count = 0
                    self._thrash_fired = False
                self._thrash_count += 1
                if (self._thrash_count > self.thrash_threshold
                        and not self._thrash_fired):
                    self._thrash_fired = True
                    self.thrash_events += 1
                    fire_attrs = dict(
                        compiles=self._thrash_count,
                        threshold=self.thrash_threshold,
                        window_s=self.thrash_window_s,
                        total_compiles=self.compiles,
                    )
        if fire_attrs is not None:
            # outside the lock: the tracer takes its own lock
            self.tracer.event("compile_thrash", **fire_attrs)

    # ------------------------------------------------------------ drain

    def drain(self) -> tuple[int, float]:
        """(compiles, compile_ms) since the previous drain — what the
        engine stamps on this tick's record."""
        with self._lock:
            out = (self._win_compiles, round(self._win_ms, 3))
            self._win_compiles = 0
            self._win_ms = 0.0
            return out

    def summary(self) -> dict:
        with self._lock:
            return {
                "compiles": self.compiles,
                "compile_ms": round(self.compile_ms, 3),
                "thrash_threshold": self.thrash_threshold,
                "thrash_window_s": self.thrash_window_s,
                "thrash_events": self.thrash_events,
            }

    @classmethod
    def from_config(cls, telemetry,
                    tracer=NULL_TRACER) -> "CompileWatchdog | None":
        """Build from a ``TelemetryConfig``; None when
        ``compile_watchdog`` is off (the engine then stamps nothing —
        byte-stable records)."""
        if not telemetry.compile_watchdog:
            return None
        return cls(
            thrash_threshold=telemetry.compile_thrash_threshold,
            thrash_window_s=telemetry.compile_thrash_window_s,
            tracer=tracer,
        )
