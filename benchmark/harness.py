"""What every kind of cell shares: files found by name, the device, the
compile cache, the in-memory span recorder, the profiler window, the result.

Nothing here knows a cell, a configuration or a metric by name: they are files
(``configs/<config>.json``, ``workloads/<cell>.json``, ``metrics/<metric>.json``
with a reader under ``readers/``), found through ``BENCHMARK.json``.
"""

from __future__ import annotations

import dataclasses
import glob
import importlib
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)
# everything a run writes goes here (git-ignored, inside the checkout)
SCRATCH = os.path.join(CHECKOUT, ".cache", "bench")


class Refused(Exception):
    """The run cannot be made as asked (no chip, unknown cell): exit 2."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with the files it names, loaded."""

    name: str
    chips: int
    config: dict  # configs/<config>.json
    workload: dict  # workloads/<cell>.json
    end_to_end: list  # manifest entries this cell reports
    per_layer: list


def load_cell(name: str, manifest_path: str, data_root: str) -> Cell:
    manifest = load_json(manifest_path)
    entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        raise Refused(f"no cell {name!r} in {manifest_path}; it has "
                      f"{[w['name'] for w in manifest['workloads']]}")
    config = load_json(os.path.join(data_root, "configs", entry["config"] + ".json"))
    workload = load_json(os.path.join(data_root, "workloads", name + ".json"))
    if workload.get("traffic") != entry["traffic"]:
        raise Refused(f"{name}: the manifest says traffic {entry['traffic']!r}, "
                      f"its file {workload.get('traffic')!r}")
    mine = lambda ms: [x for x in ms if name in x.get("workloads", [name])]
    return Cell(name=name, chips=entry["chips"], config=config,
                workload=workload, end_to_end=mine(manifest["end_to_end"]),
                per_layer=mine(manifest["per_layer"]))


def check_config(stated: dict, cfg, what: str) -> None:
    """The file holds the configuration as it is run: every key it states
    reads the same on the program's config object."""
    for key, want in stated.items():
        have = getattr(cfg, "effective_" + key, None)
        if have is None or have == 0:
            have = getattr(cfg, key)
        if isinstance(have, tuple):
            have = list(have)
        if have != want:
            raise Refused(
                f"{what}: the configuration file states {key}={want!r}, the "
                f"program runs {have!r}")


def open_cell(name: str, manifest_path: str, data_root: str, require_tpu: bool):
    """(cell, devices, the module of its kind), with the working directory at
    the checkout, where the program's relative paths resolve."""
    cell = load_cell(name, manifest_path, data_root)
    devices = configure_jax(cell.chips, require_tpu)
    kind = importlib.import_module("benchmark.kinds." + cell.workload["kind"])
    os.chdir(CHECKOUT)
    return cell, devices, kind


# ------------------------------------------------------------ device


def configure_jax(chips: int, require_tpu: bool):
    """Place the compile cache at a fixed path inside the checkout (unless
    ``JAX_COMPILATION_CACHE_DIR`` places it), find the chips, or refuse."""
    import jax

    if require_tpu:  # the CPU rehearsal tests keep no cache
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              os.path.join(CHECKOUT, ".cache", "jax"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if require_tpu:
        from benchmark.peaks import peaks_of

        if devices[0].platform != "tpu":
            raise Refused(f"needs a TPU: JAX found platform "
                          f"{devices[0].platform!r}")
        if len(devices) < chips:
            raise Refused(f"the cell needs {chips} chip(s), JAX found "
                          f"{len(devices)}")
        peaks_of(devices[0].device_kind)  # unknown kind: an error, here
    elif len(devices) < chips:
        raise Refused(f"the cell needs {chips} device(s), JAX found "
                      f"{len(devices)}")
    return devices[:chips]


def memory_peak_bytes(devices) -> int:
    """The peak on the fullest device, as the backend reports it."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def print_memory(devices, when: str) -> None:
    """The peak so far and what is held now, on the fullest device: a
    process's peak never falls, so where it rose is read from the lines."""
    held = max((d.memory_stats() or {}).get("bytes_in_use", 0) for d in devices)
    print(f"memory with {when}: peak {memory_peak_bytes(devices)} bytes, "
          f"in use {int(held)}", flush=True)


def release():
    """Drop compiled programs and whatever the caller no longer refers to."""
    import gc

    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


# ------------------------------------------------------------ spans


class SpanRecorder:
    """Host spans kept in memory; has the ``span``/``event`` surface of the
    program's ``obs.SpanTracer``, so it can be handed to the program as its
    tracer.  ``on_span(name, attrs)`` runs before a span opens: the hook by
    which a kind closes its window at a step boundary."""

    enabled = True

    def __init__(self, on_span=None):
        self.spans: list[tuple[str, float, float, dict]] = []
        self.on_span = on_span

    def span(self, name: str, **attrs):
        if self.on_span is not None:
            self.on_span(name, attrs)
        return _Span(self, name, attrs)

    def event(self, name: str, **attrs) -> None:
        t = time.perf_counter()
        self.spans.append((name, t, t, attrs))

    def write(self, record: dict) -> None:
        pass

    def preserve_history(self) -> None:
        pass

    def ring_pull(self, cursor: int = 0, limit: int = 4096) -> dict:
        return {"records": [], "cursor": cursor, "dropped": 0}

    def within(self, t0: float, t1: float, name: str | None = None):
        """Spans (name, start, end, attrs) that start inside [t0, t1)."""
        return [s for s in self.spans
                if t0 <= s[1] < t1 and (name is None or s[0] == name)]


class _Span:
    def __init__(self, rec, name, attrs):
        self.rec, self.name, self.attrs = rec, name, attrs

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.rec.spans.append(
            (self.name, self.t0, time.perf_counter(), self.attrs))
        return False


# ------------------------------------------------------------ compiles


def trace_counts(modules) -> dict:
    """{"<module>.<program>": traces so far} over the ``TRACE_COUNTS`` of the
    program modules named (dotted paths)."""
    out = {}
    for name in modules:
        mod = importlib.import_module(name)
        for k, v in mod.TRACE_COUNTS.items():
            out[f"{name.rsplit('.', 1)[-1]}.{k}"] = v
    return out


class CompileWatch:
    """What JAX hands to the backend compiler (or loads from its persistent
    cache) while the watch is open, on ``time.perf_counter``: the benchmark's
    own count of compilations, beside the program's ``TRACE_COUNTS``.  It sees
    every program, the small ones an eager ``jnp`` call makes too."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.events: list[tuple[float, float, str]] = []  # start, end, name
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            now = time.perf_counter()
            self.events.append((now - duration, now, str(kw.get("fun_name"))))

    def within(self, t0: float, t1: float) -> list:
        return [e for e in self.events if e[1] > t0 and e[0] < t1]

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on)

    def report(self, t0: float, t1: float, counted_by_program: dict) -> int:
        """Print and return the window's compilations: the program's own
        trace counts plus what the backend compiled."""
        mine = self.within(t0, t1)
        names: dict = {}
        for a, b, n in mine:
            c = names.setdefault(n, [0, 0.0])
            c[0] += 1
            c[1] += b - a
        total = sum(counted_by_program.values()) + len(mine)
        print(f"window_compiles {total}: program trace counts "
              f"{counted_by_program}; backend compilations {len(mine)} "
              f"{ {n: [c, round(s, 3)] for n, (c, s) in names.items()} }",
              flush=True)
        return total


# ------------------------------------------------------------ profiler


class TraceWindow:
    """The profiler over the last part of the measured window.

    ``start()`` is called inside the window when its remaining time falls to
    ``seconds``; ``stop()`` after the window has closed, so that writing the
    trace costs the window nothing.  A ``bench_clock_sync`` annotation ties
    the trace's clock to ``time.perf_counter``.
    """

    def __init__(self, cell: str, seconds: float):
        self.dir = os.path.join(SCRATCH, "trace", cell)
        self.seconds = seconds
        self.t_start = self.t_stop = self.t_sync = None

    @classmethod
    def of(cls, cell: "Cell", trace: bool):
        """The cell's trace window, or None in a run that is not traced."""
        if not trace:
            return None
        return cls(cell.name, float(cell.workload.get("trace_seconds", 3.0)))

    @property
    def started(self) -> bool:
        return self.t_start is not None

    def start(self) -> None:
        import shutil

        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)
        self.t_start = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench_clock_sync"):
            self.t_sync = time.perf_counter()

    def stop(self, t_window_end: float) -> None:
        import jax

        if not self.started:  # the window closed inside one long call
            raise RuntimeError(
                f"the window closed before its last {self.seconds} s could "
                f"be traced: one call of the program lasted longer")
        self.t_stop = t_window_end
        jax.profiler.stop_trace()

    def xplane(self) -> str:
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise RuntimeError(f"the profiler left no trace under {self.dir}")
        return max(found, key=os.path.getmtime)


# ------------------------------------------------------------ metrics


def read_per_layer(cell: Cell, run: dict) -> dict:
    """Each per-layer metric through the reader its file names.  A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for entry in cell.per_layer:
        spec = load_json(os.path.join(BENCH_DIR, "metrics", entry["name"] + ".json"))
        reader = importlib.import_module("benchmark.readers." + spec["reader"])
        value = reader.read(run, **spec.get("args", {}))
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def result_line(cell: Cell, run: dict, trace: bool, devices,
                trace_summary: dict | None) -> dict:
    """The contract's one JSON object."""
    if trace:
        metrics = read_per_layer(cell, run)
    else:
        metrics = {}
        for entry in cell.end_to_end:
            value = run["end_to_end"].get(entry["name"])
            if value is not None:
                metrics[entry["name"]] = {"value": float(value),
                                          "unit": entry["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": run["memory_peak_bytes"]}
    line = {"correct": bool(run["correct"]), "attempted": int(run["attempted"]),
            "failed": int(run["failed"]), "metrics": metrics, "device": device}
    if trace and trace_summary is not None:
        device["busy_s"] = trace_summary["busy_s"]
        device["window_s"] = trace_summary["window_s"]
        line["breakdown"] = {"device_ops": short_ops(trace_summary["device_ops"]),
                             "idle_gaps": trace_summary["idle_gaps"][:10]}
    line["compared"] = run["compared"]
    return line


def short_ops(device_ops: list, n: int = 10) -> list:
    """The trace names an operation by its whole HLO line; the breakdown
    keeps the name, the shape it produces and what kind it is."""
    import re

    out: dict = {}
    for name, seconds in device_ops:
        m = re.match(r"%?([\w.\-]+) = \(?(\w+\[[\d,]*\])", name)
        short = f"{m.group(1)} {m.group(2)}" if m else name[:80]
        for tag in ("tpu_custom_call", "kind=kLoop", "kind=kOutput",
                    "kind=kInput", "kind=kConvolution", "kind=kCustom"):
            if tag in name:
                short += " " + tag.replace("kind=", "")
                break
        out[short] = out.get(short, 0.0) + seconds
    return [[k, v] for k, v in sorted(out.items(), key=lambda x: -x[1])[:n]]


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """Each number compared beside its limit; correct when none passes its
    limit (an exact comparison has the limit 0)."""
    compared, ok = {}, True
    for name, limit in limits.items():
        value = values.get(name)
        good = value is not None and value == value and value <= limit
        compared[name] = {"value": value, "limit": limit}
        ok = ok and good
    return ok, compared


def print_compared(compared: dict, notes: dict | None = None) -> None:
    """The last lines on standard error."""
    if notes:
        print(f"compared where: {json.dumps(notes)}", file=sys.stderr)
    for name, c in compared.items():
        print(f"compared {name}: value {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
