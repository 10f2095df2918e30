"""The reduction from a trace to numbers: its arithmetic on hand-made events,
and the whole of it on a small trace recorded on the chip
(``data/small_trace.xplane.pb``; ``record_trace.py`` says how)."""

import json
import os

import pytest

from benchmark import trace_reduce
from conftest import DATA

MS = 1_000_000  # ns


def test_union_and_clip():
    assert trace_reduce.union([(0, 5), (3, 8), (10, 12)]) == [[0, 8], [10, 12]]
    assert trace_reduce.clip([(0, 10, "a"), (20, 30, "b"), (40, 50, "c")],
                             5, 25) == [(5, 10, "a"), (20, 25, "b")]


def test_self_time_takes_children_out_of_a_while():
    # a 10 ms while holding two 3 ms bodies, then a 2 ms fusion of its own
    ev = [(0, 10 * MS, "while"), (1 * MS, 4 * MS, "fusion.1"),
          (5 * MS, 8 * MS, "fusion.1"), (12 * MS, 14 * MS, "fusion.2")]
    got = trace_reduce.self_seconds(ev)
    assert got == pytest.approx({"while": 0.004, "fusion.1": 0.006,
                                 "fusion.2": 0.002})
    assert sum(got.values()) == pytest.approx(0.012)  # the busy time, once


def test_exposed_collective_time():
    # all-reduce 0-10 ms; compute covers 2-5 and 8-9: 6 ms exposed.  The
    # enclosing while is not "something else running".
    ev = sorted([(0, 20 * MS, "while"), (0, 10 * MS, "all-reduce.7"),
                 (2 * MS, 5 * MS, "fusion.3"), (8 * MS, 9 * MS, "copy.1"),
                 (12 * MS, 15 * MS, "fusion.3")], key=lambda x: (x[0], -x[1]))
    assert trace_reduce.exposed_seconds(ev, "all-reduce") == pytest.approx(0.006)
    assert trace_reduce.exposed_seconds(ev, "all-gather") == 0.0


def test_recorded_trace():
    """Three calls of a scanned program with 2 ms sleeps between them, as
    ``record_trace.py`` made them on a TPU v5 lite; ``small_trace.json``
    holds what that run's own clock read."""
    path = os.path.join(DATA, "small_trace.xplane.pb")
    rec = json.load(open(os.path.join(DATA, "small_trace.json")))
    s = trace_reduce.summarize(path, 1, rec["t_sync"], rec["t0"], rec["t1"],
                               host_spans=[tuple(x) for x in rec["host_spans"]])
    assert s["clock_synced"]
    assert s["window_s"] == pytest.approx(rec["t1"] - rec["t0"], rel=1e-6)
    assert 0 < s["busy_s"] < s["window_s"]
    ops = dict(s["device_ops"])
    # self time adds up to the busy time: nothing is counted twice
    assert sum(ops.values()) == pytest.approx(s["busy_s"], rel=1e-3)
    # the scan is a while whose body's operations are its children
    names = {n.split(" = ")[0].lstrip("%") for n in ops}
    assert {"while", "fusion.13", "copy.11"} <= names
    # the bodies of the scan ran inside the whiles; a while keeps
    # only what its children do not cover
    whole = [e for e in s["events"]["/device:TPU:0"] if e[2].startswith("%while")]
    assert len(whole) in (2, 3)  # a call at the window's edge may be clipped away
    in_whiles = sum(e - a for a, e, _ in whole) / 1e9
    self_of = {n.split(" = ")[0].lstrip("%"): x for n, x in ops.items()}
    assert self_of["while"] < 0.05 * in_whiles
    assert self_of["fusion.13"] > 0.5 * in_whiles
    idle = dict(s["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(s["window_s"] - s["busy_s"], rel=1e-3)
    # the device worked for microseconds of each call: the idle time falls
    # to the host span open over it, the call itself or the sleep after it
    assert idle["sleep_between_calls"] > 0.004 and idle["call"] > 0.001
    assert "no_span_open" not in idle
    mods = dict(s["modules"])
    assert any(n.startswith("jit_step") for n in mods)
    # a program's span holds its operations and the short gaps between them
    assert sum(mods.values()) == pytest.approx(s["busy_s"], rel=0.02)
