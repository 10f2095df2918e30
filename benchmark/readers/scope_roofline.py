"""A scope's share of its roofline, in %: the least time the chip could take
for the calls the traced window made (``benchmark/kernels/<kernel>.py``:
operations and bytes from the cell's shapes against the chip's peaks; further
``args`` of the metric's file go to its ``least_seconds``) over the device
seconds of the operations issued under the scope (``match`` "path": the scope
with its children).  It prints which bound, operations or bytes, holds the
least time.  Returns nothing where the trace names no such scope."""

import importlib

from benchmark import trace_scopes
from benchmark.peaks import peaks_of
from benchmark.readers import scope_share


def read(run, kernel, scope, **kernel_args):
    rows = trace_scopes.table(run)
    if rows is None:
        return None
    scope_share.print_line(run, rows)
    under = scope_share.seconds(rows, scope, match="path")
    if under <= 0:
        return None
    mod = importlib.import_module("benchmark.kernels." + kernel)
    least = mod.least_seconds(run, peaks_of(run["device_kind"]), **kernel_args)
    if least is None:
        return None
    return 100.0 * least / under
