"""What the two serving kinds share: the engine under the benchmark's weights,
the warm-up, the per-token stamps, and ``correct``.

The window drives ``ServingEngine.submit`` / ``.step`` from one thread, as the
program's own front ends do.  Every token is stamped on the host's clock when
the ``step()`` that produced it returns.

``correct``: once the window has closed, every request drained,
``memory_peak_bytes`` read and the engine freed, a sample of the finished
*greedy* requests, drawn from ``--seed`` with the longest in it, is run once
through the configuration's reference (``benchmark.reference.of``: float32,
the whole sequence at once, no cache, each layer's weights drawn from the
seed as the walk reaches it): ``logit_gap`` is the widest gap by which a served token's reference logit lies
below the reference's best at its position.  ``incomplete`` counts requests
that never finished or returned another number of tokens than asked.
"""

from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness, reference

# the program modules whose TRACE_COUNTS a serving window watches, where the
# configuration's file names no others (``trace_count_modules``)
TRACE_COUNT_MODULES = ("mamba_distributed_tpu.serving.engine",
                       "mamba_distributed_tpu.serving.prefill",
                       "mamba_distributed_tpu.ops.pallas.attention_kernels")


@dataclasses.dataclass
class Sent:
    """One request as the benchmark saw it."""

    spec: object
    request_id: int
    due: float  # perf_counter; open loop: when it was due, closed: when sent
    sent: float
    prompt: np.ndarray
    times: list = dataclasses.field(default_factory=list)  # per token
    tokens: list = dataclasses.field(default_factory=list)
    done: bool = False


def program_counters(cell):
    return harness.trace_counts(
        cell.config.get("trace_count_modules", TRACE_COUNT_MODULES))


def build_engine(cell, seed, devices, spans):
    import dataclasses as dc

    from mamba_distributed_tpu.config import get_preset
    from mamba_distributed_tpu.serving import ServingEngine

    m = cell.config["model"]
    cfg = get_preset(cell.config["preset"]).model
    if cell.config.get("serving"):
        cfg = dc.replace(cfg, **cell.config["serving"])
    harness.check_config(m, cfg, cell.name)
    harness.check_config(cell.config.get("serving", {}), cfg, cell.name)
    ref, dtype = reference.of(cell.config), reference.params_dtype(cell.config)
    params = jax.jit(lambda k: ref.init_params(k, m, dtype))(
        reference.seed_key(seed))
    harness.print_memory(devices, "the weights made")
    engine = ServingEngine(params, cfg, tracer=spans, retain_results=False,
                           **cell.workload["engine"])
    del params  # the engine keeps its own decode-layout copy
    return engine, cfg


def make_request(spec, vocab):
    from mamba_distributed_tpu.serving import GenerationRequest

    prompt = spec.prompt(vocab)
    return prompt, GenerationRequest(
        prompt_ids=prompt, max_new_tokens=spec.max_new,
        top_k=1 if spec.greedy else 50, seed=spec.index & 0x7FFFFFFF)


def absorb(events, by_id, now):
    for ev in events:
        s = by_id.get(ev.request_id)
        if s is None:
            continue
        s.times.append(now)
        s.tokens.append(ev.token)
        if ev.done:
            s.done = True


def warm_up(engine, cell, vocab, specs):
    """Every program the cell's traffic can reach, compiled or loaded from
    the cache: the cell's file lists the warm-up as phases of request sizes,
    each phase sent together and drained.  ``warmup_each_prompt_length``
    (``{"max_new": n}``) adds a phase with one request of every distinct
    prompt length among ``specs``, this run's requests: the program pads a
    prompt with eager array calls, which compile once per length, and a
    deployment in its steady state has seen every length."""
    from benchmark.traffic.requests import Spec

    phases = list(cell.workload["warmup"])
    each = cell.workload.get("warmup_each_prompt_length")
    if each:
        phases.append([{"prompt_len": n, "max_new": each["max_new"]}
                       for n in sorted({s.prompt_len for s in specs})])
    n = 0
    for phase in phases:
        for r in phase:
            n += 1
            spec = Spec(2**30 + n, r["prompt_len"], r["max_new"], bool(r.get("greedy")),
                        None, 0)
            engine.submit(make_request(spec, vocab)[1])
        while engine.pending:
            engine.step()
    # the window's latency histograms start empty
    mt = engine.metrics
    for name in ("queue_wait_ms", "ttft_ms", "itl_ms"):
        setattr(mt, name, type(getattr(mt, name))())


def drain(engine, by_id, seconds=60.0):
    t_end = time.perf_counter() + seconds
    while engine.pending and time.perf_counter() < t_end:
        events = engine.step()
        absorb(events, by_id, time.perf_counter())


# ------------------------------------------------------------ correct


def sample_for_check(sent: list, seed: int, n: int) -> list:
    """Finished greedy requests: the longest, then others drawn from the
    seed."""
    good = [s for s in sent if s.done and s.spec.greedy and s.tokens]
    if not good:
        return []
    good.sort(key=lambda s: s.spec.index)
    longest = max(good, key=lambda s: len(s.prompt) + len(s.tokens))
    rest = [s for s in good if s is not longest]
    rng = np.random.default_rng([int(seed), 7])
    pick = [rest[i] for i in rng.permutation(len(rest))[:max(0, n - 1)]]
    return [longest] + pick


def logit_gaps(sample, config, seed, control=None, pad_to=256, positions=64):
    """(program's widest gap, control's widest gap or None, tokens judged).
    Sequences are padded to a multiple of ``pad_to`` and the served positions
    to a multiple of ``positions``, so that few shapes compile."""
    logits = functools.partial(
        reference.of(config).served_logits, reference.seed_key(seed),
        config["model"], reference.params_dtype(config))
    worst, worst_control, judged = 0.0, (0.0 if control else None), 0
    for s in sample:
        seq = np.concatenate([s.prompt, np.asarray(s.tokens, np.int32)])
        n_out = len(s.tokens)
        t = -(-len(seq) // pad_to) * pad_to  # right pad: causal, no effect
        ids = np.zeros((1, t), np.int32)
        ids[0, :len(seq)] = seq
        k = -(-n_out // positions) * positions
        pos = np.full((k,), len(s.prompt) - 1, np.int32)
        pos[:n_out] = len(s.prompt) - 1 + np.arange(n_out)
        ref = np.asarray(logits(jnp.asarray(ids), jnp.asarray(pos),
                                precision="f32"))[:n_out]
        served = np.asarray(s.tokens)
        best = ref.max(axis=1)
        worst = max(worst, float((best - ref[np.arange(n_out), served]).max()))
        judged += n_out
        if control:
            low = np.asarray(logits(jnp.asarray(ids), jnp.asarray(pos),
                                    precision=control))[:n_out]
            first = low.argmax(axis=1)
            worst_control = max(worst_control, float(
                (best - ref[np.arange(n_out), first]).max()))
    return worst, worst_control, judged


def span_summary(spans, t0, t1) -> str:
    """Host spans that started in the window: count, total and longest."""
    by: dict = {}
    for name, a, b, _ in spans.within(t0, t1):
        c = by.setdefault(name, [0, 0.0, 0.0])
        c[0] += 1
        c[1] += b - a
        c[2] = max(c[2], b - a)
    return "; ".join(f"{n} x{c[0]} total {c[1]:.3f} s longest {c[2] * 1000:.1f} ms"
                     for n, c in sorted(by.items(), key=lambda x: -x[1][1]))


def finish(cell, seed, devices, engine_box, cfg, sent, spans, t0, t1, counters0,
           t_process, tw, watch, control=None):
    """Everything after the drain: counters, memory, free, check, result.
    ``engine_box`` is a one-item list holding the only reference to the
    engine, so that it is freed here, before the reference runs."""
    engine = engine_box.pop()
    m, w = cell.config["model"], cell.workload
    counters1 = program_counters(cell)
    window_compiles = watch.report(
        t0, t1, {k: counters1[k] - counters0[k] for k in counters1})
    watch.close()
    peak = harness.memory_peak_bytes(devices)
    print(f"memory_peak_bytes {peak}", flush=True)
    print(f"host spans in the window: {span_summary(spans, t0, t1)}", flush=True)
    program_metrics = engine.metrics
    capacity = engine.capacity
    tokens_per_tick = engine.tokens_per_tick
    del engine
    harness.release()

    t_ref = time.perf_counter()
    # of the requests whose last token came after the window opened
    sample = sample_for_check([s for s in sent if s.times and s.times[-1] >= t0],
                              seed, int(w["check_requests"]))
    gap, control_gap, judged = logit_gaps(
        sample, cell.config, seed, control, int(w.get("check_pad_tokens", 256)),
        int(w.get("check_pad_positions", 64)))
    finished = [s for s in sent if s.done]
    incomplete = sum(1 for s in sent
                     if not s.done or len(s.tokens) != s.spec.max_new)
    values = {"logit_gap": gap if sample else float("nan"),
              "incomplete": float(incomplete),
              "window_compiles": float(window_compiles)}
    ok, compared = harness.judge(values, w["limits"])
    print(f"reference: {len(sample)} greedy requests, {judged} served tokens, "
          f"in {time.perf_counter() - t_ref:.1f} s; control {control}: "
          f"{control_gap}", flush=True)
    harness.print_memory(devices, "the reference run")

    # model FLOPs of every prompt and output token the window processed
    ctx = lambda s: (len(s.prompt) + len(s.tokens)) / 2
    in_window = lambda s: sum(1 for x in s.times if t0 <= x < t1)
    model_flops = 0.0
    forward_flops = reference.of(cell.config).forward_flops_per_token
    for s in sent:
        f = forward_flops(m, ctx(s))
        prefilled = len(s.prompt) if s.times and t0 <= s.times[0] < t1 else 0
        model_flops += f * (prefilled + in_window(s))
    return {
        "correct": ok, "compared": compared, "control_gap": control_gap,
        "attempted": len(sent), "failed": incomplete,
        "memory_peak_bytes": peak, "spans": spans, "trace_window": tw,
        "window": (t0, t1), "chips": len(devices),
        "device_kind": devices[0].device_kind, "platform": devices[0].platform,
        "model_flops": model_flops, "program_metrics": program_metrics,
        "sent": sent, "finished": finished, "setup_s": t0 - t_process,
        "model": m, "capacity": capacity, "tokens_per_tick": tokens_per_tick,
        "serving": cell.config.get("serving", {}),
    }
