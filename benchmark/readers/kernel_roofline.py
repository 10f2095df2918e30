"""A kernel's share of its roofline, in %: the least time the chip could take
for the calls the traced window made (``benchmark/kernels/<kernel>.py``:
operations and bytes from the shapes, against the chip's peaks) over the
device time of the trace events whose names match (``line``: "ops" for
single operations, "modules" for whole compiled programs).  Returns nothing where the
trace holds no such event."""

import importlib
import re

from benchmark.peaks import peaks_of


def read(run, kernel, match, line="ops"):
    tr = run.get("trace")
    if tr is None or run["platform"] != "tpu":
        return None
    rx = re.compile(match)
    table = tr["modules"] if line == "modules" else tr["device_ops"]
    seconds = sum(s for n, s in table if rx.search(n))
    if seconds <= 0:
        return None
    mod = importlib.import_module("benchmark.kernels." + kernel)
    least = mod.least_seconds(run, peaks_of(run["device_kind"]))
    if least is None:
        return None
    return 100.0 * least / seconds
