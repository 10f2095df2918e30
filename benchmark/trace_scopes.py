"""From a device operation of a trace to the scope it was issued under.

The profiler keeps each operation's ``op_name`` (``jit(step)/while/body/ssd/
dot_general``: the program, then the ``jax.named_scope``s and transformations
it was traced under) as the stat ``tf_op`` of the plane's *event metadata*,
which ``jax.profiler.ProfileData`` does not show.  So the ``.xplane.pb`` file
is read here as what it is, the wire format of ``XSpace`` (tsl/profiler/
protobuf/xplane.proto), with nothing installed:

  XSpace.planes = 1;  XPlane: name = 2, event_metadata = 4, stat_metadata = 5
  (maps: an entry's key = 1, value = 2);  XEventMetadata: name = 2, stats = 5;
  XStatMetadata: id = 1, name = 2;  XStat: metadata_id = 1, str_value = 5,
  ref_value = 7 (the id of a stat metadata whose name is the value).

``SCOPES`` is the program's table of scope names (``mamba_distributed_tpu/
obs/scopes.py``), spelt here again because the benchmark imports nothing of
the program to decide what it measures; a test holds the two equal.
"""

from __future__ import annotations

import re

DEVICE_PLANE = "/device:TPU:"

SCOPES = (
    "embed", "layers", "attn_layers", "mixer_in_proj", "conv", "ssd",
    "chunk_local", "state_passing", "combine_chunk_outputs", "gate_norm",
    "mixer_out_proj", "attn_qkv", "attn_kernel", "kv_write", "attn_out",
    "lm_head_loss", "pool_select", "sample", "optimizer",
)
UNSCOPED = "unscoped"


def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """(field number, wire type, value) of one message: a varint's value, or
    the bytes of a length-delimited field; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield num, wire, value


def _map_values(plane, number):
    for num, wire, entry in fields(plane):
        if num == number and wire == 2:
            for n2, w2, value in fields(entry):
                if n2 == 2 and w2 == 2:
                    yield value


def op_names(path: str, stat: str = "tf_op") -> dict:
    """{operation name: op_name} over the device planes of a trace, for the
    operations whose metadata holds the stat.  The operation's name is the
    one ``ProfileData`` gives its events, so it keys ``device_ops`` too."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict = {}
    for num, wire, plane in fields(space):
        if num != 1 or wire != 2:
            continue
        name = next((bytes(v).decode() for n, w, v in fields(plane)
                     if n == 2 and w == 2), "")
        if not name.startswith(DEVICE_PLANE):
            continue
        stat_names = {}
        for meta in _map_values(plane, 5):
            f_ = {n: v for n, w, v in fields(meta)}
            stat_names[f_.get(1, 0)] = bytes(f_.get(2, b"")).decode()
        for meta in _map_values(plane, 4):
            op, found = "", None
            for n, w, v in fields(meta):
                if n == 2 and w == 2:
                    op = bytes(v).decode()
                elif n == 5 and w == 2:
                    s = {k: x for k, _, x in fields(v)}
                    if stat_names.get(s.get(1)) != stat:
                        continue
                    if 5 in s:
                        found = bytes(s[5]).decode()
                    elif 7 in s:
                        found = stat_names.get(s[7])
            if op and found:
                out[op] = found
    return out


# ------------------------------------------------------------ scopes


def program_of(op_name: str) -> str:
    """``jit(_tick)/...`` -> ``jit__tick``, the name of the compiled program
    as the trace's line of whole programs has it."""
    head = op_name.split("/", 1)[0]
    m = re.match(r"(\w+)\((.*)\)$", head)
    return f"{m.group(1)}_{m.group(2)}" if m else head


def _parts(op_name: str) -> list:
    """The path as the profiler wrote it: a fusion of two sources keeps both
    op_names, ``a;b`` (the first is taken), and the last ends with ``:``."""
    return op_name.split(";")[0].rstrip(":").split("/")


def components(op_name: str) -> list:
    """The names along an op_name's path with the transformations' wrappers
    taken off: ``transpose(jvp(ssd))`` and ``ssd`` both read ``ssd``."""
    out = []
    for part in _parts(op_name):
        while (m := re.match(r"[\w.\-]+\((.*)\)$", part)):
            part = m.group(1)
        out.append(part)
    return out


def path_scopes(op_name: str | None, scopes=SCOPES) -> list:
    """Every name of the table along the path, outermost first (the first
    component is the program, which is no scope)."""
    if not op_name:
        return []
    return [p for p in components(op_name)[1:] if p in scopes]


def scope_of(op_name: str | None, scopes=SCOPES) -> str:
    """The innermost name of the table along the path, or ``unscoped``."""
    return (path_scopes(op_name, scopes) or [UNSCOPED])[-1]


# ------------------------------------------------------------ attribution


def _common(paths: list) -> str | None:
    """The longest path all of ``paths`` start with, by whole components."""
    if not paths:
        return None
    split = [_parts(p) for p in paths]
    n = 0
    while all(len(s) > n for s in split) and len({s[n] for s in split}) == 1:
        n += 1
    return "/".join(split[0][:n]) or None


def attribute(events, names: dict) -> dict:
    """{operation: [path or None, self seconds]} of one device's events
    (sorted by start, longest first, as ``trace_reduce`` keeps them).

    An operation's path is its own op_name.  The compiler's own operations
    (layout copies, hoisted converts, the ``while`` itself) carry none: such
    an operation is filed where it ran, under the path that the named
    operations of the innermost enclosing ``while`` have in common
    (``jit(_tick)/layers/while/body/closed_call``); one that ran inside no
    named loop keeps none and counts as ``unscoped``.
    """
    out: dict = {}
    # [start, end, name, covered, children's paths, waiting nameless]
    stack: list = []

    def emit(op, path, seconds):
        rec = out.setdefault(op, [path, 0.0])
        rec[1] += seconds

    def close(item):
        s, e, name, covered, below, waiting = item
        mine = max(0.0, (e - s) - covered) / 1e9
        path = names.get(name)
        if path is None:
            waiting.append((name, mine))
            path = _common(below)
        else:
            emit(name, path, mine)
        parent = stack[-1] if stack else None
        if path is not None:
            for op, sec in waiting:
                emit(op, path, sec)
            if parent is not None:
                parent[4].append(path)
        elif parent is not None:
            parent[5].extend(waiting)
        else:
            for op, sec in waiting:
                emit(op, None, sec)

    for s, e, name in events:
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][1]) - s
        stack.append([s, e, name, 0.0, [], []])
    while stack:
        close(stack.pop())
    return out


def table(run) -> list | None:
    """[[operation, path or None, self seconds]] of a traced run, largest
    first, the seconds a mean over its devices; kept on the run.  None where
    there is no device trace."""
    tr = run.get("trace")
    if tr is None or run.get("platform") != "tpu":
        return None
    if "_scope_table" not in run:
        names = op_names(run["trace_window"].xplane())
        merged: dict = {}
        for events in tr["events"].values():
            for op, (path, sec) in attribute(events, names).items():
                rec = merged.setdefault(op, [path, 0.0])
                rec[1] += sec / len(tr["events"])
        run["_scope_table"] = sorted(
            ([op, path, sec] for op, (path, sec) in merged.items()),
            key=lambda r: -r[2])
    return run["_scope_table"]


def by_scope(rows, scopes=SCOPES) -> dict:
    """{scope: seconds}, each operation under the innermost name once, with
    every name of the table and ``unscoped``."""
    out = {s: 0.0 for s in (*scopes, UNSCOPED)}
    for _, path, sec in rows:
        out[scope_of(path, scopes)] += sec
    return out
