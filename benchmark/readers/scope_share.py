"""Share, in %, of device time spent in the operations issued under a scope of
the program's table (``benchmark/trace_scopes.py``): their self seconds over
the device's busy seconds (``of`` "busy") or over the seconds of the compiled
programs whose names match ``of`` (``^jit__tick``; only operations of those
programs are then counted).

``scope`` is a regular expression.  ``match`` "innermost" (the default) tests
it against the one name an operation is filed under, the innermost of the
table in its path: ``^layers$`` is then the layer scan's *own* operations, its
slices and stacked write-backs, and nothing of the blocks inside it.  ``match``
"path" tests every name along the path: ``^ssd$`` is then the whole scan with
its children.  ``but`` leaves out the operations whose own HLO line matches it
(``tpu_custom_call``: what lies under ``attn_kernel`` and is no kernel).

The first call of a traced run prints the line ``device time by scope: ...``,
every name of the table and ``unscoped``, largest first.  Returns nothing
where the trace names no such scope, as with a program that enters none.
"""

import re

from benchmark import trace_scopes


def print_line(run, rows) -> None:
    if run.get("_scope_line"):
        return
    run["_scope_line"] = True
    busy = run["trace"]["busy_s"]
    by = trace_scopes.by_scope(rows)
    print("device time by scope: " + ", ".join(
        f"{name} {sec:.4f} s ({100 * sec / busy:.1f} %)"
        for name, sec in sorted(by.items(), key=lambda x: -x[1]))
        + f"; busy {busy:.4f} s", flush=True)
    loose = [(op, sec) for op, path, sec in rows
             if trace_scopes.scope_of(path) == trace_scopes.UNSCOPED][:8]
    print("unscoped, by operation: " + ", ".join(
        f"{op.split(' = ')[0].lstrip('%')} {sec:.4f}" for op, sec in loose),
        flush=True)


def seconds(rows, scope: str, match: str = "innermost", but: str | None = None,
            program: str | None = None) -> float:
    """Self seconds of the rows the arguments select."""
    rx = re.compile(scope)
    no = re.compile(but) if but else None
    prog = re.compile(program) if program else None
    total = 0.0
    for op, path, sec in rows:
        if path is None or (no is not None and no.search(op)):
            continue
        if prog is not None and not prog.search(trace_scopes.program_of(path)):
            continue
        found = (trace_scopes.path_scopes(path) if match == "path"
                 else [trace_scopes.scope_of(path)])
        if any(rx.search(name) for name in found):
            total += sec
    return total


def read(run, scope, of="busy", match="innermost", but=None):
    rows = trace_scopes.table(run)
    if rows is None:
        return None
    print_line(run, rows)
    if of == "busy":
        mine, whole = seconds(rows, scope, match, but), run["trace"]["busy_s"]
    else:
        rx = re.compile(of)
        mine = seconds(rows, scope, match, but, program=of)
        whole = sum(s for n, s in run["trace"]["modules"] if rx.search(n))
    if mine <= 0 or whole <= 0:
        return None
    return 100.0 * mine / whole
