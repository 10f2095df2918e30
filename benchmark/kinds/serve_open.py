"""Kind ``serve_open``: independent users — requests are sent when they are
due, whether or not earlier ones have finished, at the rate fixed in the
cell's file.

One thread: submit what is due, then one ``engine.step()``; sleep only when
nothing is pending.  Time to first token is taken from when the request was
*due*, so a stall shows as the wait it imposes; how late the generator ran is
printed.  After the window closes nothing more is sent and every request that
was due in it is waited for (up to a minute): a late answer is late, not wrong.
"""

from __future__ import annotations

import json
import time

import numpy as np

from benchmark import harness
from benchmark.kinds import _serve
from benchmark.traffic import requests as traffic


def percentile(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q))


def run(cell, seed, seconds, trace, devices, t_process, control=None):
    w, m = cell.workload, cell.config["model"]
    spans = harness.SpanRecorder()
    engine, cfg = _serve.build_engine(cell, seed, devices, spans)
    vocab = m["vocab_size"]
    specs = traffic.open_loop(w["mix"], seed, seconds)
    prepared = [_serve.make_request(s, vocab) for s in specs]
    _serve.warm_up(engine, cell, vocab, specs)
    tw = harness.TraceWindow.of(cell, trace)
    counters0 = _serve.program_counters(cell)
    watch = harness.CompileWatch()

    sent, by_id, lateness = [], {}, []
    nxt, n = 0, len(specs)
    t0 = time.perf_counter()
    t1 = t0 + seconds

    def send(i, now):
        prompt, req = prepared[i]
        s = _serve.Sent(specs[i], engine.submit(req), t0 + specs[i].due_s,
                        now, prompt)
        sent.append(s)
        by_id[s.request_id] = s
        lateness.append(now - s.due)

    while True:
        now = time.perf_counter()
        if now >= t1:
            break
        while nxt < n and t0 + specs[nxt].due_s <= now:
            send(nxt, now)
            nxt += 1
        if tw is not None and not tw.started and t1 - now <= tw.seconds:
            tw.start()
        if engine.pending:
            events = engine.step()
            _serve.absorb(events, by_id, time.perf_counter())
        elif nxt < n:
            time.sleep(max(0.0, min(t0 + specs[nxt].due_s, t1) - time.perf_counter()))
        else:
            time.sleep(max(0.0, t1 - time.perf_counter()))
    if tw is not None:
        tw.stop(t1)
    # due inside the window but not yet sent (the last step ran past them):
    # they are late, and they count
    while nxt < n:
        send(nxt, time.perf_counter())
        nxt += 1
    _serve.drain(engine, by_id)

    box = [engine]
    del engine
    run = _serve.finish(cell, seed, devices, box, cfg, sent, spans, t0, t1,
                        counters0, t_process, tw, watch, control)
    worst = 1000.0 * (60.0 + seconds)  # a request that never answered
    ttft = [1000.0 * (s.times[0] - s.due) if s.times else worst for s in sent]
    gaps = [1000.0 * (b - a) for s in sent for a, b in zip(s.times, s.times[1:])]
    late = np.asarray(lateness) if lateness else np.zeros(1)
    print(f"open loop: {len(sent)} of {n} requests sent, {len(run['finished'])} "
          f"finished; generator lateness ms p50 {1000 * np.median(late):.3f} "
          f"p95 {1000 * np.percentile(late, 95):.3f} max {1000 * late.max():.3f}; "
          f"queue at close {sum(1 for s in sent if not s.times or s.times[0] >= t1)}",
          flush=True)
    print(f"ttft ms mean {np.mean(ttft):.2f} p50 {percentile(ttft, 50):.2f} p95 {percentile(ttft, 95):.2f} "
          f"p99 {percentile(ttft, 99):.2f}; itl ms p50 {percentile(gaps, 50):.3f} "
          f"p95 {percentile(gaps, 95):.3f} p99 {percentile(gaps, 99):.3f} "
          f"({len(gaps)} gaps)", flush=True)
    # every request: [index, prompt tokens, ms sent after it was due, ttft ms]
    print("ttft by request " + json.dumps(
        [[s.spec.index, len(s.prompt), round(1000 * (s.sent - s.due)), round(x)]
         for s, x in zip(sent, ttft)], separators=(",", ":")), flush=True)
    done_tokens = sum(len(s.prompt) + len(s.tokens) for s in run["finished"]
                      if s.times[-1] < t1)
    run["end_to_end"] = {
        "ttft_p50_ms": percentile(ttft, 50), "ttft_p95_ms": percentile(ttft, 95),
        "itl_p95_ms": percentile(gaps, 95),
        "serve_tokens_per_s": done_tokens / seconds, "setup_s": run["setup_s"],
    }
    run["ttft_ms"] = ttft
    run["backlog_at_close"] = sum(1 for s in sent if not s.done or s.times[-1] >= t1)
    return run
