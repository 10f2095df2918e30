"""From a profiler trace (``.xplane.pb``) to numbers.

Reads the trace with ``jax.profiler.ProfileData`` alone.  A device is a plane
whose name starts ``/device:TPU:``; its operations are the events of the line
``XLA Ops``.  A ``while`` (a scan over layers) holds its body's operations as
nested events on the same line, so time by name is *self* time: an event's
duration less what its children cover.

* ``busy_s``: the union of the intervals in which an operation ran, inside
  the traced window, averaged over the devices used.
* ``device_ops``: self seconds by operation name (mean over devices).
* ``idle_gaps``: idle seconds inside the window by the host span open at the
  gap's middle (the program's spans, placed on the trace's clock through the
  ``bench_clock_sync`` annotation), largest first.
* ``modules``: seconds by compiled program (the line ``XLA Modules``).
* ``exposed(match)``: seconds in which an operation matching ``match`` ran and
  no other operation did.
"""

from __future__ import annotations

import bisect
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SYNC_NAME = "bench_clock_sync"
MIN_GAP_NS = 20_000  # shorter idle gaps are the device's own, not the host's


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def device_events(data, only_line: str = OPS_LINE) -> dict:
    """{plane name: [(start_ns, end_ns, name)]} sorted by start."""
    out = {}
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        for line in plane.lines:
            if line.name != only_line:
                continue
            ev = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for e in line.events]
            ev.sort(key=lambda x: (x[0], -x[1]))
            out.setdefault(plane.name, []).extend(ev)
    return out


def find_sync_ns(data) -> float | None:
    """Start of the ``bench_clock_sync`` annotation on the trace's clock."""
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == SYNC_NAME:
                    return e.start_ns + e.duration_ns
    return None


def union(intervals) -> list:
    """Merged (start, end) intervals from intervals sorted by start."""
    out = []
    for s, e in intervals:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(events, w0: float, w1: float):
    return [(max(s, w0), min(e, w1), n) for s, e, n in events
            if e > w0 and s < w1]


def self_seconds(events) -> dict:
    """{name: seconds} of self time; ``events`` sorted by (start, -end)."""
    out: dict = {}
    stack = []  # [start, end, name, covered_by_children]

    def close(item):
        s, e, n, covered = item
        out[n] = out.get(n, 0.0) + max(0.0, (e - s) - covered) / 1e9

    for s, e, n in events:
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][1]) - s
        stack.append([s, e, n, 0.0])
    while stack:
        close(stack.pop())
    return out


def exposed_seconds(events, match: str) -> float:
    """Seconds in which events matching ``match`` ran with nothing else."""
    rx = re.compile(match)
    mine = union(sorted((s, e) for s, e, n in events if rx.search(n)))
    # leaf events only: a parent ``while`` is not "something else running"
    leaves = _leaves(events)
    other = union(sorted((s, e) for s, e, n in leaves if not rx.search(n)))
    total, j = 0.0, 0
    for s, e in mine:
        cur = s
        while j < len(other) and other[j][1] <= s:
            j += 1
        k = j
        while k < len(other) and other[k][0] < e:
            if other[k][0] > cur:
                total += other[k][0] - cur
            cur = max(cur, other[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total / 1e9


def _leaves(events):
    out = []
    for i, (s, e, n) in enumerate(events):
        nxt = events[i + 1] if i + 1 < len(events) else None
        if nxt is None or nxt[0] >= e:
            out.append((s, e, n))
    return out


def summarize(path: str, n_devices: int, t_sync: float, t0: float, t1: float,
              host_spans=()) -> dict:
    """The numbers of one traced window.  ``t_sync``, ``t0``, ``t1`` and the
    host spans' times are ``time.perf_counter`` readings; the window is
    [t0, t1]."""
    data = load(path)
    per_plane = device_events(data)
    if not per_plane:
        raise RuntimeError(f"{path}: no device plane with a line {OPS_LINE!r}; "
                           f"planes: {[p.name for p in data.planes]}")
    sync_ns = find_sync_ns(data)
    planes = sorted(per_plane)[:n_devices]
    if sync_ns is None:
        # no annotation found: take the window from the operations themselves
        w0 = min(per_plane[p][0][0] for p in planes)
        w1 = max(e for p in planes for _, e, _ in per_plane[p])
        to_ns = None
    else:
        to_ns = lambda t: sync_ns + (t - t_sync) * 1e9
        w0, w1 = to_ns(t0), to_ns(t1)
    busy, ops, idle, clipped = [], {}, {}, {}
    modules: dict = {}
    per_plane_modules = device_events(data, MODULES_LINE)
    for p in planes:
        for s_, e_, n_ in clip(per_plane_modules.get(p, []), w0, w1):
            modules[n_] = modules.get(n_, 0.0) + (e_ - s_) / 1e9 / len(planes)
    for p in planes:
        ev = clip(per_plane[p], w0, w1)
        clipped[p] = ev
        merged = union([(s, e) for s, e, _ in ev])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for n, sec in self_seconds(ev).items():
            ops[n] = ops.get(n, 0.0) + sec / len(planes)
        if p == planes[0]:
            gaps, cur = [], w0
            for s, e in merged:
                if s > cur:
                    gaps.append((cur, s))
                cur = max(cur, e)
            if cur < w1:
                gaps.append((cur, w1))
            spans_ns = [] if to_ns is None else sorted(
                (to_ns(a), to_ns(b), name) for name, a, b, _ in host_spans
                if to_ns(b) > w0 and to_ns(a) < w1)
            starts = [a for a, _, _ in spans_ns]
            for s, e in gaps:
                if e - s < MIN_GAP_NS:
                    who = "between_operations"
                else:
                    # the innermost host span open at the gap's middle: the
                    # last one to start before it that has not yet ended
                    mid, who = (s + e) / 2, "no_span_open"
                    for k in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
                        if spans_ns[k][1] > mid:
                            who = spans_ns[k][2]
                            break
                idle[who] = idle.get(who, 0.0) + (e - s) / 1e9
    return {
        "busy_s": sum(busy) / len(busy), "window_s": (w1 - w0) / 1e9,
        "device_ops": [[n, s] for n, s in
                       sorted(ops.items(), key=lambda x: -x[1])],
        "idle_gaps": [[n, s] for n, s in
                      sorted(idle.items(), key=lambda x: -x[1])],
        "modules": [[n, s] for n, s in
                    sorted(modules.items(), key=lambda x: -x[1])],
        "events": clipped, "clock_synced": sync_ns is not None,
    }
