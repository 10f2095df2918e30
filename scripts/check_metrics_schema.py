"""Metrics-schema drift gate: code vs docs/OBSERVABILITY.md.

The fabric's Prometheus schema lives in ONE place — the family
constructors in ``obs/prom.py`` — and its documentation lives in the
"Live telemetry plane" metric table of docs/OBSERVABILITY.md.  This
gate (a committed table checked against the code, names not numbers)
fails CI when the two drift:

  1. render a fully-featured synthetic fabric exposition (every
     optional block present: KV pages, goodput, compile watchdog, all
     three latency histograms, obs-plane counters) and parse it back,
     so the emitted-family set is derived from the REAL encoder, not a
     hand-kept list;
  2. extract every ``mamba_*`` name from the doc table;
  3. fail on any family emitted but undocumented (the doc rotted), and
     on any documented but never emitted (the doc oversells).

Exit 0 = in sync.  Wired into tests/test_cli.py under the ``metrics``
marker.

Usage:
  python scripts/check_metrics_schema.py [--doc docs/OBSERVABILITY.md]
"""

from __future__ import annotations

import argparse
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mamba_distributed_tpu.obs import prom  # noqa: E402

_DEFAULT_DOC = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "docs", "OBSERVABILITY.md",
)

# one synthetic histogram with a low, mid and overflow bucket occupied
_HIST = {"lo": 0.5, "hi": 512.0, "growth": 1.5,
         "count": 3, "total": 30.0,
         "counts": {"0": 1, "5": 1, "20": 1}}

# a summary with EVERY optional block populated, so every gated family
# in replica_families() emits at least one sample
_FULL_SUMMARY = {
    "ticks": 10, "decode_tokens": 80, "decode_tokens_per_sec": 100.0,
    "mean_tick_ms": 5.0, "mean_slot_occupancy": 0.5,
    "mean_queue_depth": 1.0, "finished_requests": 4, "preemptions": 1,
    "migrations": {"out": 1, "in": 2},
    "kv_pages": {"used": 3, "capacity": 8, "peak_used": 5,
                 "allocs": 9, "frees": 6},
    "goodput": {"useful_fraction": 0.9, "goodput_tokens_per_sec": 90.0,
                "serving_mfu": 0.1},
    "compile": {"compiles": 2, "compile_ms": 120.0},
    "tuning": {"quota_stalls": 1, "hot_swaps": 1, "jobs_submitted": 2,
               "jobs_completed": 1, "jobs_failed": 1, "train_steps": 20,
               "deploys": 1, "yields": 3, "last_loss": 4.2},
}


def emitted_families() -> set[str]:
    """Every family name the encoder can emit, derived by rendering a
    maximally-featured synthetic fabric and parsing it back."""
    snapshot = {
        "replica": 0, "role": "mixed", "summary": _FULL_SUMMARY,
        "histograms": {"queue_wait_ms": _HIST, "ttft_ms": _HIST,
                       "itl_ms": _HIST, "tune_step_ms": _HIST},
        "stats": {"depth": 2, "resident": 3, "capacity": 4},
    }
    text = prom.render_fabric(
        [snapshot], replicas=1, accepting=1, ready=True,
        obs_records_pulled=10, obs_records_dropped=1,
        queue_depth=3,
        sheds={"queue_cap": 2, "queue_deadline": 5},
        autoscale={"scale_ups": 1, "scale_downs": 1},
        tune_queue_depth=2,
    )
    return set(prom.parse_exposition(text))


def documented_families(doc_path: str) -> set[str]:
    """Every ``mamba_*`` metric name in the doc's table rows (a name in
    prose does not count — the TABLE is the schema of record)."""
    names: set[str] = set()
    with open(doc_path) as f:
        for line in f:
            if not line.lstrip().startswith("|"):
                continue
            for name in re.findall(r"`(mamba_[a-z0-9_]+)`", line):
                names.add(name)
    return names


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--doc", default=_DEFAULT_DOC,
                    help="metric-table source of record")
    args = ap.parse_args(argv)

    emitted = emitted_families()
    documented = documented_families(args.doc)
    undocumented = sorted(emitted - documented)
    stale = sorted(documented - emitted)

    rel = os.path.relpath(args.doc)
    if undocumented:
        print(f"UNDOCUMENTED ({len(undocumented)}): emitted by obs/prom.py "
              f"but missing from the {rel} metric table:")
        for name in undocumented:
            print(f"  {name}")
    if stale:
        print(f"STALE ({len(stale)}): documented in {rel} but never "
              f"emitted by obs/prom.py:")
        for name in stale:
            print(f"  {name}")
    if undocumented or stale:
        return 1
    print(f"metrics schema ok: {len(emitted)} families match {rel}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
