"""The scope readers: the wire-format walk on the recorded traces, the filing
of operations under scopes on hand-made events, the readers on hand-made runs
and on ``data/scoped_trace.xplane.pb`` (``record_scoped_trace.py`` says how it
was recorded, on the chip), and the SSD counts by hand."""

import json
import os

import pytest

from benchmark import harness, trace_reduce, trace_scopes
from benchmark.kernels import ssd_chunked
from benchmark.readers import event_attr, scope_roofline, scope_share
from conftest import DATA

SMALL = os.path.join(DATA, "small_trace.xplane.pb")
SCOPED = os.path.join(DATA, "scoped_trace.xplane.pb")
MS = 1_000_000  # ns


# ------------------------------------------------------------ the walk


def short(names: dict) -> dict:
    return {op.split(" = ")[0].lstrip("%"): v for op, v in names.items()}


def test_wire_reader_finds_the_dots_op_name():
    names = trace_scopes.op_names(SMALL)
    assert short(names)["fusion.13"] == \
        "jit(step)/while/body/closed_call/dot_general:"
    # the operation's name is the event's name, so it keys device_ops
    ops = dict(trace_reduce.summarize(SMALL, 1, 0.0, 0.0, 1.0)["device_ops"])
    assert set(names) <= set(ops)


@pytest.mark.parametrize("path", [SMALL, SCOPED], ids=["small", "scoped"])
@pytest.mark.parametrize("stat", ["tf_op", "hlo_category", "source"])
def test_wire_reader_equals_the_protobuf_reading(path, stat):
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    want = {}
    for plane in space.planes:
        if not plane.name.startswith(trace_scopes.DEVICE_PLANE):
            continue
        stats = {k: v.name for k, v in plane.stat_metadata.items()}
        for meta in plane.event_metadata.values():
            for s in meta.stats:
                if stats[s.metadata_id] == stat:
                    want[meta.name] = (s.str_value if s.WhichOneof("value")
                                       == "str_value" else stats[s.ref_value])
    assert want and trace_scopes.op_names(path, stat) == want


def test_table_is_the_programs():
    from mamba_distributed_tpu.obs import scopes

    assert trace_scopes.SCOPES == scopes.ALL


# ------------------------------------------------------------ names


@pytest.mark.parametrize("op_name,program,innermost,along", [
    ("jit(_tick)/layers/while/body/closed_call/layers/while/body/ssd/mul:",
     "jit__tick", "ssd", ["layers", "layers", "ssd"]),
    ("jit(step_fn)/while/body/closed_call/transpose(jvp(layers))/while/body/"
     "closed_call/checkpoint/rematted_computation/ssd/chunk_local/dot_general:",
     "jit_step_fn", "chunk_local", ["layers", "ssd", "chunk_local"]),
    ("jit(step_fn)/while/body/closed_call/transpose(jvp(lm_head_loss))/mul",
     "jit_step_fn", "lm_head_loss", ["lm_head_loss"]),
    ("jit(prefill_chunk)/attn_layers/while/body/dynamic_update_slice:",
     "jit_prefill_chunk", "attn_layers", ["attn_layers"]),
    ("jit(step)/jvp(layers)/while/body/ssd/reshape;checkpoint/ssd/reshape",
     "jit_step", "ssd", ["layers", "ssd"]),
    # the program is no scope, nor is an operation that shares a name
    ("jit(layers)/while/body/closed_call/add:", "jit_layers", "unscoped", []),
    (None, None, "unscoped", []),
])
def test_scope_of_an_op_name(op_name, program, innermost, along):
    assert trace_scopes.scope_of(op_name) == innermost
    assert trace_scopes.path_scopes(op_name) == along
    if op_name:
        assert trace_scopes.program_of(op_name) == program


def test_nameless_operations_are_filed_where_they_ran():
    # a program: a sample op, then a loop holding a named op, a nameless
    # copy and an inner loop (nameless itself) with a named op and a nameless
    # one; a nameless copy outside every loop
    names = {"sample": "jit(_tick)/layers/while/body/closed_call/sample/top_k:",
             "select": "jit(_tick)/layers/while/body/closed_call/pool_select/select_n:",
             "mul": "jit(_tick)/layers/while/body/closed_call/layers/while/body/ssd/mul:"}
    ev = sorted([(0, 100 * MS, "while.outer"), (1 * MS, 3 * MS, "sample"),
                 (10 * MS, 20 * MS, "select"), (20 * MS, 30 * MS, "copy.158"),
                 (30 * MS, 90 * MS, "while.inner"), (31 * MS, 61 * MS, "mul"),
                 (61 * MS, 81 * MS, "copy.62"),
                 (110 * MS, 120 * MS, "copy.40")],
                key=lambda x: (x[0], -x[1]))
    got = trace_scopes.attribute(ev, names)
    filed = {op: trace_scopes.scope_of(path) for op, (path, _) in got.items()}
    assert filed == {"sample": "sample", "select": "pool_select", "mul": "ssd",
                     "copy.62": "ssd",  # all the inner loop's names agree
                     "while.inner": "ssd", "copy.158": "layers",
                     "while.outer": "layers", "copy.40": "unscoped"}
    assert got["copy.40"][0] is None
    # self seconds, each counted once: they add up to the busy time
    assert sum(sec for _, sec in got.values()) == pytest.approx(0.110)
    assert got["while.inner"][1] == pytest.approx(0.010)
    assert got["while.outer"][1] == pytest.approx(0.018)


# ------------------------------------------------------------ readers


def handmade_run():
    tick = "jit(_tick)/layers/while/body/closed_call"
    chunk = "jit(prefill_chunk)/layers/while/body"
    rows = [["%select.5 = f32[64,96]", f"{tick}/pool_select/select_n:", 1.2],
            ["%dus.2 = f32[64,96]", f"{tick}/layers/while/body/dynamic_update_slice:", 0.8],
            ["%mul.10 = f32[96]", f"{tick}/layers/while/body/closed_call/ssd/mul:", 0.4],
            ["%copy.44 = bf16[64]", None, 0.1],
            ["%fusion.7 = bf16[1]", f"{chunk}/ssd/chunk_local/dot_general:", 0.3],
            ["%dus.9 = f32[64,1]", f"{chunk}/dynamic_update_slice:", 0.2],
            ["%call.3 = bf16[2] custom-call(), custom_call_target=\"tpu_custom_call\"",
             f"{chunk}/attn_kernel/ragged/pallas_call:", 0.5],
            ["%copy.9 = bf16[2]", f"{chunk}/attn_kernel/transpose:", 0.5]]
    return {"platform": "tpu", "_scope_table": rows, "_scope_line": True,
            "trace": {"busy_s": 4.0, "modules": [["jit__tick(1)", 2.5],
                                                 ["jit_prefill_chunk(2)", 1.5]]}}


@pytest.mark.parametrize("args,want", [
    (dict(scope="^(pool_select|layers)$", of="^jit__tick"), 100 * 2.0 / 2.5),
    (dict(scope="^(pool_select|layers)$"), 100 * 2.2 / 4.0),
    (dict(scope="^ssd$", match="path"), 100 * 0.7 / 4.0),
    (dict(scope="^ssd$"), 100 * 0.4 / 4.0),  # chunk_local is filed deeper
    (dict(scope="^attn_kernel$", but="tpu_custom_call"), 100 * 0.5 / 4.0),
    (dict(scope="^attn_kernel$"), 100 * 1.0 / 4.0),
    (dict(scope="^optimizer$"), None),  # nothing to read: left out, not 0
    (dict(scope="^ssd$", of="^jit_train"), None),
])
def test_scope_share_on_a_handmade_run(args, want):
    got = scope_share.read(handmade_run(), **args)
    assert got == (None if want is None else pytest.approx(want))


def test_scope_line_names_every_scope_and_unscoped(capsys):
    run = handmade_run()
    del run["_scope_line"]
    scope_share.read(run, scope="^ssd$")
    scope_share.read(run, scope="^layers$")  # the line is printed once a run
    out = capsys.readouterr().out
    assert out.count("device time by scope:") == 1
    line = out.splitlines()[0]
    for name in (*trace_scopes.SCOPES, trace_scopes.UNSCOPED):
        assert f" {name} " in line
    assert line.index("pool_select 1.2000 s (30.0 %)") < line.index("unscoped 0.1000 s (2.5 %)")
    assert "unscoped, by operation: copy.44 0.1000" in out


def test_readers_return_nothing_without_a_device_trace():
    assert scope_share.read({"platform": "cpu", "trace": None}, scope="^ssd$") is None
    assert scope_share.read({"platform": "tpu"}, scope="^ssd$") is None
    assert scope_roofline.read({"platform": "tpu"}, kernel="ssd_chunked",
                               scope="^ssd$", config="mamba2-280m") is None


def test_a_program_without_scopes_reads_nothing():
    """The parent commit's programs enter no scope: every new device metric
    is left out of its line, none reads 0 and none raises."""
    run = handmade_run()
    run["_scope_table"] = [[op, None if p is None else
                            "jit(_tick)/while/body/closed_call/mul:", sec]
                           for op, p, sec in run["_scope_table"]]
    for name in os.listdir(os.path.join(harness.BENCH_DIR, "metrics")):
        spec = harness.load_json(os.path.join(harness.BENCH_DIR, "metrics", name))
        if spec["reader"] == "scope_share":
            assert scope_share.read(run, **spec["args"]) is None, name
        elif spec["reader"] == "scope_roofline":
            assert scope_roofline.read(run, **spec["args"]) is None, name


def recorded_run():
    rec = json.load(open(os.path.join(DATA, "scoped_trace.json")))

    class Window:
        t_start, t_stop = rec["t0"], rec["t1"]
        xplane = staticmethod(lambda: SCOPED)

    tr = trace_reduce.summarize(SCOPED, 1, rec["t_sync"], rec["t0"], rec["t1"],
                                host_spans=[tuple(x) for x in rec["host_spans"]])
    return {"platform": "tpu", "trace": tr, "trace_window": Window}


def test_scope_share_on_the_recorded_trace(capsys):
    """What the chip's trace keeps of the scopes (``record_scoped_trace.py``):
    the names survive fusion, the scan, ``jax.checkpoint`` and the transpose."""
    run = recorded_run()
    rows = trace_scopes.table(run)
    by_op = {op.split(" = ")[0].lstrip("%"): path for op, path, _ in rows}
    paths = [p for p in by_op.values() if p]
    assert any("/jvp(layers)/while/body/" in p and "/ssd/" in p for p in paths)
    assert any("/transpose(jvp(layers))/while/body/" in p and
               "/checkpoint/rematted_computation/ssd/" in p for p in paths)
    assert any("/transpose(jvp(lm_head_loss))/" in p for p in paths)
    # the named pallas_call is an operation of that name, still a custom call
    kernel = next(op for op, _, _ in rows if op.startswith("%scale_kernel"))
    assert "tpu_custom_call" in kernel
    assert trace_scopes.scope_of(by_op[kernel.split(" = ")[0].lstrip("%")]) == "attn_kernel"
    # the whiles carry no op_name of their own: filed by what ran in them
    whiles = [op for op in by_op if op.startswith("while")]
    assert whiles and all(trace_scopes.scope_of(by_op[w]) == "layers" for w in whiles)
    by = trace_scopes.by_scope(rows)
    busy = run["trace"]["busy_s"]
    assert sum(by.values()) == pytest.approx(busy, rel=1e-3)
    assert by["unscoped"] < 0.25 * busy
    for scope in ("ssd", "layers", "lm_head_loss", "gate_norm", "attn_kernel"):
        assert by[scope] > 0, scope
    share = scope_share.read(run, scope="^ssd$", match="path")
    assert share == pytest.approx(100 * by["ssd"] / busy)
    inner = scope_share.read(run, scope="^layers$")
    whole = scope_share.read(run, scope="^layers$", match="path")
    assert 0 < inner < whole < 100
    assert scope_share.read(run, scope="^attn_kernel$", but="tpu_custom_call") is None
    assert scope_share.read(run, scope="^ssd$", of="^jit_step") == pytest.approx(
        100 * by["ssd"] / dict(run["trace"]["modules"]).popitem()[1])
    assert "device time by scope:" in capsys.readouterr().out


def test_annotations_land_on_the_host_line():
    """``TraceAnnotation`` and ``StepTraceAnnotation`` (the program's tracer
    opens them beside its spans) are events of the host's python line."""
    data = trace_reduce.load(SCOPED)
    host = {e.name for plane in data.planes
            if not plane.name.startswith(trace_scopes.DEVICE_PLANE)
            for line in plane.lines for e in line.events}
    assert {"bench_clock_sync", "train_step", "train"} <= host


def test_event_attr_takes_the_windows_events():
    spans = harness.SpanRecorder()
    run = {"spans": spans, "window": (10.0, 20.0)}
    assert event_attr.read(run, event="serving_first_token",
                           attr="prefill_wait_ms", q=95) is None
    for t, wait in [(5.0, 900.0), (11.0, 0.0), (12.0, 10.0), (19.0, 20.0),
                    (25.0, 30.0)]:  # the warm-up's; three in; one in the drain
        spans.spans.append(("serving_first_token", t, t,
                            {"prefill_wait_ms": wait, "chunks": 0}))
    spans.spans.append(("other", 12.0, 12.0, {"prefill_wait_ms": 1e6}))
    read = lambda q: event_attr.read(run, event="serving_first_token",
                                     attr="prefill_wait_ms", q=q)
    assert read(50) == pytest.approx(15.0) and read(100) == 30.0
    assert event_attr.read(run, event="serving_first_token",
                           attr="first_tick_wait_ms", q=50) is None


# ------------------------------------------------------------ the SSD


def test_ssd_chunked_counts_by_hand():
    m = {"d_model": 8, "expand": 2, "headdim": 4, "ngroups": 1, "d_state": 3,
         "d_conv": 4, "vocab_size": 16, "n_layer": 2, "chunk_size": 4}
    # T 8 in chunks of 4 (nc 2); h = 16 / 4 = 4 heads of p 4; g 1; n 3
    ops, by = ssd_chunked.forward_call(m, 8)
    g_ = 2 * 4 * 4 * 3 * 2 * 1          # C B^T: l l n per chunk and group
    y_diag = 2 * 4 * 4 * 4 * 2 * 4      # l l p per chunk and head
    states = 2 * 4 * 3 * 4 * 2 * 4      # l n p per chunk and head
    y_off = states
    passing = 2 * 2 * 2 * 4 * 3 * 4     # nc nc p n per head
    cumsums = 2 * (2 * 4 * 4 * 2 * 4)   # two of l l per chunk and head
    assert ops == g_ + y_diag + states + y_off + passing + cumsums == 3648
    # x and y: 8 x 4 x 4 bf16 each; B, C: 8 x 1 x 3 bf16 each; dt: 8 x 4 f32
    assert by == 2 * (8 * 16 * 2) + 2 * (8 * 3 * 2) + 8 * 4 * 4 == 736
    # a step: the forward, remat's second forward, a backward of two forwards
    assert ssd_chunked.step_calls(m, 8, 2, 1) == (4 * ops, 4 * by)
    assert ssd_chunked.step_calls(m, 8, 1, 1) == (3 * ops, 3 * by)


def test_ssd_chunked_least_seconds_of_a_traced_window(capsys):
    """1.5 steps of the real configuration inside the traced window."""
    spans = harness.SpanRecorder()
    spans.spans += [("train_step", 0.0, 1.0, {}), ("train_step", 1.0, 2.0, {}),
                    ("train_step", 2.0, 3.0, {}), ("data_load", 1.0, 1.0, {})]

    class Window:
        t_start, t_stop = 1.5, 3.0

    run = {"spans": spans, "trace_window": Window, "tokens": 10 * 65536,
           "attempted": 10, "chips": 4}
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    m = harness.load_json(os.path.join(harness.BENCH_DIR, "configs",
                                       "mamba2-280m.json"))["model"]
    ops, by = ssd_chunked.forward_call(m, 1024)
    calls = 1.5 * 16 * 64  # steps x sequences a chip and step x layers
    by_ops, by_bytes = ssd_chunked.bounds(run, peaks, "mamba2-280m")
    assert by_ops == pytest.approx(calls * 4 * ops / 197e12)
    assert by_bytes == pytest.approx(calls * 4 * by / 819e9)
    assert ssd_chunked.least_seconds(run, peaks, "mamba2-280m") == max(by_ops, by_bytes)
    assert "bound by operations" in capsys.readouterr().out
    # 1.68 GFLOP and 6.9 MB a sequence and layer: 247 operations a byte,
    # just over the chip's 240
    assert ops / by == pytest.approx(247.2, rel=1e-3)
    run["spans"] = harness.SpanRecorder()
    assert ssd_chunked.least_seconds(run, peaks, "mamba2-280m") is None
