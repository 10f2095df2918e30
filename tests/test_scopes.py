"""Device scopes, request-phase events and the profiler-annotated tracer.

The scope names of ``obs/scopes.py`` are metadata on the traced operations:
each jitted program a benchmark cell runs is compiled here, on the CPU at a
tiny size, and every name its path can reach has to appear as a component of
an ``op_name`` in the compiled HLO (``jvp(ssd)`` and ``transpose(jvp(ssd))``
count as ``ssd``: a transformation wraps the component; the lowered MLIR
keeps a called function's names relative, so it is the HLO that is read).
Nothing else may move: the programs keep their names and each lowering traces
once.
"""

import dataclasses
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.trace_scopes import components  # wrappers off: jvp(ssd) -> ssd
from mamba_distributed_tpu.config import ModelConfig
from mamba_distributed_tpu.models import init_lm_params
from mamba_distributed_tpu.obs import (
    NULL_TRACER,
    AnnotatedTracer,
    annotated,
    scopes,
)
from mamba_distributed_tpu.serving import GenerationRequest, ServingEngine
from mamba_distributed_tpu.utils.metrics import ServingMetrics

MAMBA = ModelConfig(d_model=32, n_layer=2, vocab_size=64, ssm_layer="mamba2",
                    headdim=8, chunk_size=16, d_state=16,
                    compute_dtype="float32", prefill_chunk_tokens=16,
                    prefill_tokens_per_tick=16)
# one attention layer per two: the periodic superstep scans, as hybrid-280m
HYBRID = dataclasses.replace(
    MAMBA, n_layer=4, attn_layer_idx=(1, 3), attn_num_heads=4,
    attn_num_kv_heads=2, attn_head_dim=8, kv_page_tokens=8,
    kv_slot_tokens=64)

MIXER = {scopes.EMBED, scopes.LAYERS, scopes.MIXER_IN_PROJ, scopes.CONV,
         scopes.SSD, scopes.GATE_NORM, scopes.MIXER_OUT_PROJ,
         scopes.LM_HEAD_LOSS}
CHUNKED = {scopes.CHUNK_LOCAL, scopes.STATE_PASSING,
           scopes.COMBINE_CHUNK_OUTPUTS}
ATTN = {scopes.ATTN_QKV, scopes.ATTN_KERNEL, scopes.ATTN_OUT}

# path -> (the jitted program's name, the scopes its operations can carry)
PATHS = {
    "train_step.mamba2": ("jit_step_fn", MIXER | CHUNKED | {scopes.OPTIMIZER}),
    "train_step.hybrid": ("jit_step_fn",
                          MIXER | CHUNKED | ATTN | {scopes.OPTIMIZER}),
    "tick.mamba2": ("jit__tick",
                    MIXER | {scopes.SAMPLE, scopes.POOL_SELECT}),
    "tick.hybrid": ("jit__tick", MIXER | ATTN | {
        scopes.ATTN_LAYERS, scopes.KV_WRITE, scopes.SAMPLE,
        scopes.POOL_SELECT}),
    "prefill.mamba2": ("jit__prefill", MIXER | CHUNKED),
    "prefill_chunk.mamba2": ("jit_prefill_chunk", MIXER | CHUNKED),
    "prefill_chunk.hybrid": ("jit_prefill_chunk", MIXER | CHUNKED | ATTN | {
        scopes.ATTN_LAYERS, scopes.KV_WRITE}),
    "pool_insert.mamba2": ("jit_insert", {scopes.POOL_SELECT}),
}


def _lower(path: str):
    """(lowered program, {trace counter: bumps this lowering made})."""
    from mamba_distributed_tpu.serving import engine as engine_mod
    from mamba_distributed_tpu.serving import prefill as prefill_mod
    from mamba_distributed_tpu.serving import state_cache
    from mamba_distributed_tpu.training import train_step as train_step_mod

    what, kind = path.split(".")
    cfg = HYBRID if kind == "hybrid" else MAMBA
    params = jax.eval_shape(lambda k: init_lm_params(k, cfg),
                            jax.random.PRNGKey(0))
    if what == "train_step":
        from mamba_distributed_tpu.parallel.mesh import build_mesh
        from mamba_distributed_tpu.training.optimizer import make_optimizer
        from tests.test_parallel import make_cfg

        tcfg = make_cfg("/nonexistent", micro=2, accum=2, T=32)
        tcfg = dataclasses.replace(tcfg, model=dataclasses.replace(
            cfg, remat=True, loss_impl="blocked", loss_vocab_blocks=2))
        mesh = build_mesh(tcfg.mesh, jax.devices()[:1])
        optimizer = make_optimizer(tcfg)
        place = lambda t: jax.tree.map(
            lambda a: jax.device_put(jnp.zeros(a.shape, a.dtype),
                                     jax.devices()[0]), t)
        params = place(params)
        opt = place(jax.eval_shape(optimizer.init, params))
        step = train_step_mod.make_train_step(tcfg, optimizer, mesh, params,
                                              opt)
        x = jax.ShapeDtypeStruct((2, 2, 32), jnp.int32)
        counts = train_step_mod.TRACE_COUNTS
        before = dict(counts)
        low = step.lower(params, opt, x, x)
        return low, {k: counts[k] - before[k] for k in counts}
    capacity = 2
    pool = jax.eval_shape(lambda: state_cache.init_pool(cfg, capacity, 1))
    if what == "tick":
        args = [params, pool]
        if cfg.attn_layer_idx:
            args += [jax.ShapeDtypeStruct((capacity, cfg.kv_pages_per_slot),
                                          jnp.int32),
                     jax.ShapeDtypeStruct((capacity,), jnp.int32)]
        counts = engine_mod.TRACE_COUNTS
        before = dict(counts)
        low = engine_mod._tick.lower(*args, cfg=cfg, k_max=8, steps=2,
                                     mesh=None, n_micro=None)
    elif what == "prefill":
        ids = jax.ShapeDtypeStruct((1, 16), jnp.int32)
        mask = jax.ShapeDtypeStruct((1, 16), jnp.float32)
        counts = engine_mod.TRACE_COUNTS
        before = dict(counts)
        low = engine_mod._prefill.lower(params, ids, mask, cfg=cfg)
    elif what == "prefill_chunk":
        state = jax.eval_shape(lambda p: state_cache.read_state(p, 0), pool)
        if cfg.attn_layer_idx:
            state["attn_blocks"] = pool["state"]["attn_blocks"]
            state["attn_meta"] = (
                jax.ShapeDtypeStruct((1, cfg.kv_pages_per_slot), jnp.int32),
                jax.ShapeDtypeStruct((1,), jnp.int32))
        ids = jax.ShapeDtypeStruct((1, 16), jnp.int32)
        mask = jax.ShapeDtypeStruct((1, 16), jnp.float32)
        counts = prefill_mod.TRACE_COUNTS
        before = dict(counts)
        low = prefill_mod.prefill_chunk.lower(params, ids, mask, state,
                                              cfg=cfg, mesh=None)
    else:
        state = jax.eval_shape(lambda p: state_cache.read_state(p, 0), pool)
        logits = jax.ShapeDtypeStruct((1, cfg.vocab_size_padded), jnp.float32)
        counts, before = {}, {}
        low = state_cache.insert.lower(
            pool, 0, state, logits, jax.random.PRNGKey(0), 4, 1, 1.0, -1)
    return low, {k: counts[k] - before[k] for k in counts}


_LOWERED: dict = {}


def lowered(path: str):
    """(compiled HLO, its op_names, trace-counter bumps); once a module."""
    if path not in _LOWERED:
        low, bumps = _lower(path)
        text = low.compile().as_text()
        _LOWERED[path] = (text, op_names(text), bumps)
    return _LOWERED[path]


def op_names(text: str) -> set:
    return set(re.findall(r'op_name="([^"]+)"', text))


CASES = [(p, s) for p, (_, reach) in PATHS.items() for s in sorted(reach)]


@pytest.mark.parametrize("path,scope", CASES,
                         ids=[f"{p}-{s}" for p, s in CASES])
def test_scope_reaches_the_lowered_program(path, scope):
    _, names, _ = lowered(path)
    assert any(scope in components(n)[1:] for n in names), (
        f"{path}: no operation carries the scope {scope!r}")


@pytest.mark.parametrize("path", sorted(PATHS))
def test_program_names_and_trace_counts_are_what_they_were(path):
    text, _, bumps = lowered(path)
    program = PATHS[path][0]
    assert text.startswith(f"HloModule {program},"), text[:80]
    # one lowering is one trace of the program's own function, no more
    own = {"train_step": "train_step", "tick": "tick", "prefill": "prefill",
           "prefill_chunk": "chunk"}.get(path.split(".")[0])
    if own is not None:
        assert bumps[own] == 1 and sum(bumps.values()) == 1, bumps


@pytest.mark.parametrize("transform", ["jvp", "transpose"])
def test_blocked_loss_backward_carries_the_scope(transform):
    """A custom_vjp's rules are traced apart from the call site: the blocked
    loss names its forward and its backward itself."""
    from mamba_distributed_tpu.ops.loss import blocked_cross_entropy

    f = jax.jit(jax.grad(
        lambda n, h, t: blocked_cross_entropy(n, h, t, 2, jnp.float32),
        argnums=(0, 1)))
    text = f.lower(jnp.ones((2, 4, 8)), jnp.ones((16, 8)),
                   jnp.zeros((2, 4), jnp.int32)).compile().as_text()
    mine = [n for n in op_names(text)
            if scopes.LM_HEAD_LOSS in components(n)[1:]]
    want = {"jvp": r"/jvp\(lm_head_loss\)/",
            "transpose": r"/transpose\(jvp\(lm_head_loss\)\)/"}[transform]
    assert any(re.search(want, n) and "dot_general" in n for n in mine), mine


def test_train_step_backward_and_remat_keep_the_scopes():
    _, names, _ = lowered("train_step.mamba2")
    ssd = [n for n in names if scopes.SSD in components(n)[1:]]
    assert any("transpose(" in n for n in ssd)
    assert any("rematted_computation" in n or "checkpoint" in n for n in ssd)
    loss = [n for n in names if scopes.LM_HEAD_LOSS in components(n)[1:]]
    assert any("transpose(" in n for n in loss)


def test_table_is_constants_only():
    assert len(set(scopes.ALL)) == len(scopes.ALL) == 19
    # the grouping scopes stand beside the table, not in it (the benchmark
    # holds ``ALL`` equal to its own copy, which only a benchmark PR edits)
    assert scopes.BLOCK_PARTS == ("ssm_branch", "attn_branch", "mlp",
                                  "moe", "router", "experts")
    assert not set(scopes.BLOCK_PARTS) & set(scopes.ALL)
    public = {k: v for k, v in vars(scopes).items()
              if k.isupper() and k not in ("ALL", "BLOCK_PARTS")}
    assert set(public.values()) == set(scopes.ALL) | set(scopes.BLOCK_PARTS)
    assert all(re.fullmatch(r"[a-z_]+", s) for s in public.values())


# ------------------------------------------------------------ the wrapper


class Recorder:
    """A tracer that keeps what it is given (the surface of SpanTracer)."""

    enabled = True

    def __init__(self, refuse=None):
        self.spans, self.events, self.open, self.refuse = [], [], 0, refuse

    def span(self, name, **attrs):
        if name == self.refuse:
            raise RuntimeError("refused")
        return _Span(self, name, attrs)

    def event(self, name, **attrs):
        self.events.append((name, attrs))


class _Span:
    def __init__(self, rec, name, attrs):
        self.rec, self.name, self.attrs = rec, name, attrs

    def __enter__(self):
        self.rec.open += 1
        return self

    def __exit__(self, *exc):
        self.rec.open -= 1
        self.rec.spans.append((self.name, self.attrs))
        return False


def test_annotated_tracer_forwards_and_wraps_once():
    rec = Recorder(refuse="no")
    tr = annotated(rec, step_span="train_step")
    assert isinstance(tr, AnnotatedTracer) and annotated(tr) is tr
    assert tr.enabled is True and annotated(NULL_TRACER).enabled is False
    with tr.span("data_load", step=3):
        assert rec.open == 1
    with tr.span("train_step", step=3):  # the profiler's step annotation
        pass
    tr.event("marker", a=1)
    assert rec.spans == [("data_load", {"step": 3}), ("train_step", {"step": 3})]
    assert rec.events == [("marker", {"a": 1})] and rec.open == 0
    with pytest.raises(RuntimeError, match="refused"):
        tr.span("no")  # the tracer's own refusal passes through
    with pytest.raises(ZeroDivisionError):
        with tr.span("boom"):
            1 / 0
    assert rec.open == 0 and rec.spans[-1][0] == "boom"


def test_annotated_span_shows_in_a_profiler_capture(tmp_path):
    import glob

    from jax.profiler import ProfileData

    tr = annotated(Recorder(), step_span="train_step")
    jax.profiler.start_trace(str(tmp_path))
    with tr.span("serving_tick", occupied=1):
        pass
    with tr.span("train_step", step=7):
        pass
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    seen = {e.name for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events}
    assert "serving_tick" in seen and "train" in seen


# ------------------------------------------------------------ the engine


def _engine(tracer, jsonl=None, **kw):
    params = init_lm_params(jax.random.PRNGKey(0), MAMBA)
    metrics = ServingMetrics(capacity=2, jsonl_path=jsonl)
    return ServingEngine(params, MAMBA, capacity=2, tokens_per_tick=2,
                         metrics=metrics, tracer=tracer, **kw)


def _requests():
    # 8 tokens: one-shot; 40: three chunks of 16 through the chunk queue
    return [GenerationRequest(prompt_ids=np.arange(n, dtype=np.int32) % 60,
                              max_new_tokens=5, key=jax.random.PRNGKey(n))
            for n in (8, 40, 9, 24)]


def test_outputs_bit_equal_with_and_without_the_wrapper():
    wrapped = _engine(Recorder())
    assert isinstance(wrapped.tracer, AnnotatedTracer)
    bare = _engine(Recorder())
    bare._tracer = bare._tracer._inner  # the tracer as it was handed in
    assert not isinstance(bare.tracer, AnnotatedTracer)
    a, b = wrapped.run(_requests()), bare.run(_requests())
    for x, y in zip(a, b):
        assert np.array_equal(x.new_tokens, y.new_tokens)
    assert [n for n, _ in wrapped.tracer._inner.spans] == \
        [n for n, _ in bare.tracer.spans]


def test_compile_cache_is_keyed_by_scopes():
    """A cached executable keeps the op_names it was compiled under: the
    engine and the trainer switch JAX's key to hold them (seen on the chip:
    a train step cached before the scopes read 100 % unscoped)."""
    flag = "jax_compilation_cache_include_metadata_in_key"
    keep = getattr(jax.config, flag)
    try:
        jax.config.update(flag, False)
        _engine(NULL_TRACER)
        assert getattr(jax.config, flag) is True
    finally:
        jax.config.update(flag, keep)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    jsonl = str(tmp_path_factory.mktemp("served") / "serving.jsonl")
    rec = Recorder()
    eng = _engine(rec, jsonl)
    eng.run(_requests())
    with open(jsonl) as f:
        records = [json.loads(line) for line in f]
    return rec, [r for r in records if r["kind"] == "request"]


@pytest.mark.parametrize("prompt_tokens,chunks", [(8, 0), (40, 3)],
                         ids=["one_shot", "chunked"])
def test_first_token_waits_sum_to_the_ttft(served, prompt_tokens, chunks):
    rec, requests = served
    first = [a for n, a in rec.events if n == "serving_first_token"]
    assert len(first) == 4  # one event a request, nothing per token
    ev = next(a for a in first if a["prompt_tokens"] == prompt_tokens)
    assert ev["chunks"] == chunks
    waits = [ev["queue_wait_ms"], ev["prefill_wait_ms"],
             ev["first_tick_wait_ms"]]
    assert all(w >= 0 for w in waits)
    record = next(r for r in requests if r["request_id"] == ev["request"])
    assert record["trace_id"] == ev["trace"]
    assert sum(waits) == pytest.approx(record["ttft_ms"], abs=2e-3)
    assert ev["queue_wait_ms"] == pytest.approx(record["queue_wait_ms"],
                                                abs=2e-3)
    if chunks:  # the chunk queue is where a long prompt waits
        assert ev["prefill_wait_ms"] > 0
    else:
        assert ev["prefill_wait_ms"] == 0


@pytest.mark.parametrize("attr", ["occupied", "live", "prefill_tokens"])
def test_serving_tick_span_attributes(served, attr):
    rec, _ = served
    ticks = [a for n, a in rec.spans if n == "serving_tick"]
    assert ticks and all(attr in a for a in ticks)
    assert all(0 <= a["live"] <= a["occupied"] <= 2 for a in ticks)
    # 8 + 40 + 9 + 24 prompt tokens: 8 and 9 one-shot, 40 and 24 in chunks
    # of 16 (3 and 2): everything the prefill phase dispatched is on a tick
    assert sum(a["prefill_tokens"] for a in ticks) == 8 + 9 + 16 * 5


def test_serving_emit_follows_every_tick(served):
    rec, _ = served
    names = [n for n, _ in rec.spans if n in ("serving_tick", "serving_emit")]
    assert names[::2] == ["serving_tick"] * (len(names) // 2)
    assert names[1::2] == ["serving_emit"] * (len(names) // 2)


# ------------------------------------------------------------ the trainer


def test_trainer_spans_train_log_and_takes_a_tracer(tmp_path):
    from mamba_distributed_tpu.config import TelemetryConfig
    from mamba_distributed_tpu.training import Trainer
    from tests.test_parallel import make_cfg

    cfg = dataclasses.replace(make_cfg(tmp_path, micro=4, accum=1, T=32),
                              telemetry=TelemetryConfig(sentinel=False))
    t = Trainer(cfg, verbose=False)
    assert isinstance(t.tracer, AnnotatedTracer)  # NULL_TRACER underneath
    rec = Recorder()
    t.tracer = rec  # as the benchmark hands its recorder over
    assert isinstance(t.tracer, AnnotatedTracer) and t.tracer._inner is rec
    t.run(max_steps=2)
    names = [n for n, _ in rec.spans if n != "eval"]
    assert names == ["data_load", "train_step", "train_log"] * 2
    assert [a["step"] for n, a in rec.spans if n == "train_log"] == [0, 1]
