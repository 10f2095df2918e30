"""A scope's share of its roofline inside one compiled program, in %, the
scope found by PATH: as ``program_scope_roofline`` (the least time the chip
could take for the calls the traced window made,
``benchmark/kernels/<kernel>.py``, over the device seconds of the operations
issued under the scope inside the programs whose names match ``program``),
for a scope that the benchmark's own table (``trace_scopes.SCOPES``) does not
hold: the operation's ``op_name`` is asked for a component matching ``scope``
(the wrappers of transformations taken off), as ``path_share`` asks it.
Returns nothing where the trace names no such scope, as with a program that
enters none."""

import importlib
import re

from benchmark import trace_scopes
from benchmark.peaks import peaks_of
from benchmark.readers import scope_share


def seconds(rows, scope: str, program: str) -> float:
    rx, prog = re.compile(scope), re.compile(program)
    return sum(sec for _, path, sec in rows
               if path and prog.search(trace_scopes.program_of(path))
               and any(rx.search(c) for c in trace_scopes.components(path)[1:]))


def read(run, kernel, scope, program, **kernel_args):
    rows = trace_scopes.table(run)
    if rows is None:
        return None
    scope_share.print_line(run, rows)
    under = seconds(rows, scope, program)
    if under <= 0:
        return None
    mod = importlib.import_module("benchmark.kernels." + kernel)
    least = mod.least_seconds(run, peaks_of(run["device_kind"]), **kernel_args)
    if least is None:
        return None
    print(f"{scope} under {program}: {under:.4f} s on the device, least "
          f"{least:.4f} s", flush=True)
    return 100.0 * least / under
