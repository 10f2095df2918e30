"""Kind ``serve_closed``: a fixed number of clients, each sending its next
request when its last one has finished — a batch pipeline with fixed
concurrency.  Judged on the tokens (prompt plus output) of every request that
finished inside the window over the window's seconds; its tails are printed
and decide nothing.

The clients draw, in order, from the cell's population of request sizes in
this seed's permutation, over and over.  They start ``ramp_seconds`` (the
cell's file) before the window opens, as set-up: an engine that starts empty
prefills every slot at once and finishes nothing for a while, then everything
together, and a window opened there measures where those waves fall.  A
request counts where it finishes.  When the window closes nothing more is
sent and what is in flight is waited for.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from benchmark import harness
from benchmark.kinds import _serve
from benchmark.traffic import requests as traffic


def run(cell, seed, seconds, trace, devices, t_process, control=None):
    w, m = cell.workload, cell.config["model"]
    spans = harness.SpanRecorder()
    engine, cfg = _serve.build_engine(cell, seed, devices, spans)
    vocab = m["vocab_size"]
    population = traffic.closed_loop(w["mix"], seed)
    clients = int(w["mix"]["clients"])
    _serve.warm_up(engine, cell, vocab, population)
    tw = harness.TraceWindow.of(cell, trace)
    counters0 = _serve.program_counters(cell)
    watch = harness.CompileWatch()

    sent, by_id = [], {}
    nxt = 0
    t_ramp = time.perf_counter()
    ramp = float(w.get("ramp_seconds", 0.0))
    t0 = t1 = None
    in_flight = 0
    while True:
        now = time.perf_counter()
        while in_flight < clients:
            spec = dataclasses.replace(population[nxt % len(population)], index=nxt)
            prompt, req = _serve.make_request(spec, vocab)
            rid = engine.submit(req)
            s = _serve.Sent(spec, rid, now, now, prompt)
            sent.append(s)
            by_id[rid] = s
            nxt += 1
            in_flight += 1
        if tw is not None and t0 is not None and not tw.started \
                and t0 + seconds - now <= tw.seconds:
            tw.start()
        events = engine.step()
        now = time.perf_counter()
        _serve.absorb(events, by_id, now)
        finished = sum(1 for ev in events if ev.done)
        in_flight -= finished
        if t0 is None:
            if finished and now - t_ramp >= ramp:
                t0 = now  # these completions lie before the window
                counters0 = _serve.program_counters(cell)
        elif (finished and now - t0 >= seconds) or now - t0 >= seconds + 15.0:
            t1 = now  # these completions lie inside it
            break
    if tw is not None:
        tw.stop(t1)
    _serve.drain(engine, by_id)

    box = [engine]
    del engine
    run = _serve.finish(cell, seed, devices, box, cfg, sent, spans, t0, t1,
                        counters0, t_process, tw, watch, control)
    inside = [s for s in run["finished"] if t0 < s.times[-1] <= t1]
    done_tokens = sum(len(s.prompt) + len(s.tokens) for s in inside)
    ttft = [1000.0 * (s.times[0] - s.sent) for s in sent if s.times]
    gaps = [1000.0 * (b - a) for s in sent for a, b in zip(s.times, s.times[1:])]
    print(f"closed loop: {clients} clients, {len(sent)} requests sent "
          f"({sum(1 for x in sent if x.sent < t0)} in the {t0 - t_ramp:.1f} s "
          f"ramp), {len(inside)} finished inside the {t1 - t0:.3f} s window; "
          f"ttft ms p50 {np.percentile(ttft, 50):.1f} p95 {np.percentile(ttft, 95):.1f}; "
          f"itl ms p50 {np.percentile(gaps, 50):.3f} p95 {np.percentile(gaps, 95):.3f}",
          flush=True)
    run["end_to_end"] = {"serve_tokens_per_s": done_tokens / (t1 - t0),
                         "setup_s": run["setup_s"]}
    return run
