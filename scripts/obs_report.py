"""Telemetry report: phase-time breakdown + latency percentiles from jsonl.

Ingests any mix of the repo's jsonl event streams — MetricsLogger's
``metrics.jsonl`` (kind train/val), the span tracer's ``events.jsonl``
(kind span/event), and ServingMetrics' serving stream (kind
serving_tick/request) — and prints:

  * a span phase-time breakdown (where the host loop actually spends
    its time: data_load vs train_step vs eval vs checkpoint_save, or
    serving_admit vs serving_tick);
  * train-step statistics (steps, loss movement, step time, tokens/sec);
  * serving tick statistics (occupancy, tick time, decode tokens/sec)
    plus goodput: useful tokens vs computed-but-wasted token lanes,
    goodput tokens/sec and the host-computed serving MFU the engine
    stamps on every tick record;
  * per-request latency percentiles: queue-wait / TTFT / end-to-end
    exactly (the scalars are in the records), inter-token latency by
    merging the per-request streaming histograms each record carries
    (obs/histogram.py — p50/p95/p99 without any stored samples) — per
    replica AND merged fabric-wide when the records are
    replica-stamped;
  * SLO attainment: when an obs/slo.py monitor stamped its targets
    (slo_config event) into the stream, the per-metric attainment
    table plus the breach/recovery transitions.

Usage:
  python scripts/obs_report.py log/events.jsonl log/metrics.jsonl
  python scripts/obs_report.py serving.jsonl --json

docs/OBSERVABILITY.md documents the event schema.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mamba_distributed_tpu.obs.export import load_jsonl  # noqa: E402
from mamba_distributed_tpu.obs.histogram import StreamingHistogram  # noqa: E402


def load_events(paths: list[str]) -> list[dict]:
    """All parseable records from all files, in file order.  Unparseable
    lines are counted, not fatal — a crashed writer can leave a torn
    final line, and the report must still come out."""
    events, bad = [], []
    for path in paths:
        events.extend(load_jsonl(path, bad_lines=bad))
    if bad:
        print(f"warning: skipped {len(bad)} unparseable line(s)",
              file=sys.stderr)
    return events


def _pcts(values: list[float]) -> dict:
    """Exact nearest-rank percentiles of scalar samples."""
    if not values:
        return {"count": 0, "mean": None, "p50": None, "p95": None,
                "p99": None, "max": None}
    xs = sorted(values)
    pick = lambda q: xs[min(len(xs) - 1, max(0, -(-q * len(xs) // 100) - 1))]
    return {
        "count": len(xs),
        "mean": round(sum(xs) / len(xs), 3),
        "p50": round(pick(50), 3),
        "p95": round(pick(95), 3),
        "p99": round(pick(99), 3),
        "max": round(xs[-1], 3),
    }


def build_report(events: list[dict]) -> dict:
    """Aggregate the event stream into one report dict (the ``--json``
    output; ``format_report`` renders it as tables)."""
    report: dict = {}

    # --- spans: per-name totals; share-% over top-level (depth-0) time
    spans = [e for e in events if e.get("kind") == "span"]
    if spans:
        by_name: dict[str, dict] = {}
        for s in spans:
            d = by_name.setdefault(s["name"], {
                "count": 0, "total_ms": 0.0, "max_ms": 0.0,
                "depth": s.get("depth", 0),
            })
            d["count"] += 1
            d["total_ms"] += s.get("dur_ms", 0.0)
            d["max_ms"] = max(d["max_ms"], s.get("dur_ms", 0.0))
        top_total = sum(
            s.get("dur_ms", 0.0) for s in spans if s.get("depth", 0) == 0
        )
        for d in by_name.values():
            d["total_ms"] = round(d["total_ms"], 3)
            d["mean_ms"] = round(d["total_ms"] / d["count"], 3)
            d["share"] = (
                round(d["total_ms"] / top_total, 4)
                if top_total and d["depth"] == 0 else None
            )
        report["spans"] = dict(sorted(
            by_name.items(), key=lambda kv: -kv[1]["total_ms"]
        ))

    # --- train/val records (MetricsLogger metrics.jsonl)
    train = [e for e in events if e.get("kind") == "train"]
    if train:
        losses = [e["loss"] for e in train if e.get("loss") is not None]
        report["train"] = {
            "steps": len(train),
            "first_loss": losses[0] if losses else None,
            "last_loss": losses[-1] if losses else None,
            "non_finite_losses": sum(1 for e in train if e.get("loss") is None),
            "step_ms": _pcts([e["step_ms"] for e in train
                              if e.get("step_ms") is not None]),
            "mean_tokens_per_sec": (
                round(sum(e["tokens_per_sec"] for e in train) / len(train), 1)
                if all(e.get("tokens_per_sec") is not None for e in train)
                else None
            ),
        }
    vals = [e for e in events if e.get("kind") == "val"]
    if vals:
        report["val"] = {"count": len(vals), "last_loss": vals[-1].get("loss")}

    # --- serving ticks (ServingMetrics jsonl stream)
    ticks = [e for e in events if e.get("kind") == "serving_tick"]
    if ticks:
        tokens = sum(e.get("tokens_emitted", 0) for e in ticks)
        total_ms = sum(e.get("tick_ms", 0.0) for e in ticks)
        # per-tick ratios, so streams from runs with different capacities
        # mix correctly ("any mix" is the advertised contract)
        ratios = [e["occupied"] / e["capacity"] for e in ticks
                  if e.get("capacity") and e.get("occupied") is not None]
        # chunked-prefill accounting (absent in pre-chunking streams):
        # per-tick-record prefill stall + chunk tokens dispatched in
        # that window.  Zero-stall records (no prefill work) are
        # excluded from the percentiles.  NB the granularity differs
        # from ServingMetrics.summary()["prefill_stall_ms"]: that
        # histogram samples per ENGINE STEP, while a tick record merges
        # any preceding tick-less steps into one window, so the two
        # views' counts/percentiles legitimately differ (totals agree).
        stalls = [e["prefill_stall_ms"] for e in ticks
                  if e.get("prefill_stall_ms")]
        chunk_tokens = sum(e.get("prefill_chunk_tokens", 0) for e in ticks)
        # chunk dispatch throughput over chunk DISPATCH time (same
        # definition as summary()["prefill_chunk_tokens_per_sec"]) —
        # stall time additionally contains one-shot admissions
        chunk_total_ms = sum(e.get("prefill_chunk_ms", 0.0) for e in ticks)
        # hybrid paged-KV gauges (absent in pure-SSM streams): pool
        # occupancy per tick + total allocator churn in the stream
        kv_ticks = [e for e in ticks if e.get("kv_pages_used") is not None]
        kv_pages = None
        if kv_ticks:
            cap = kv_ticks[-1].get("kv_pages_capacity")
            kv_pages = {
                "capacity": cap,
                "peak_used": max(e["kv_pages_used"] for e in kv_ticks),
                "mean_used": round(
                    sum(e["kv_pages_used"] for e in kv_ticks)
                    / len(kv_ticks), 2
                ),
                "allocs": sum(e.get("kv_page_allocs", 0) for e in kv_ticks),
                "frees": sum(e.get("kv_page_frees", 0) for e in kv_ticks),
            }
        # prefix-state cache gauges (absent unless a cache-enabled
        # engine wrote the stream): window hit/miss/saved-token
        # counters summed, occupancy gauges from the last record
        pticks = [e for e in ticks if e.get("prefix_hits") is not None]
        prefix = None
        if pticks:
            p_hits = sum(e["prefix_hits"] for e in pticks)
            p_misses = sum(e.get("prefix_misses", 0) for e in pticks)
            prefix = {
                "hits": p_hits,
                "misses": p_misses,
                "hit_rate": (
                    round(p_hits / (p_hits + p_misses), 4)
                    if p_hits + p_misses else None
                ),
                "saved_prefill_tokens": sum(
                    e.get("prefix_saved_tokens", 0) for e in pticks
                ),
                "entries": pticks[-1].get("prefix_cache_entries"),
                "bytes": pticks[-1].get("prefix_cache_bytes"),
            }
        preemptions = sum(e.get("preemptions", 0) for e in ticks)
        # disaggregated-tier handoffs (absent unless a disagg fabric
        # wrote the stream): fabric-wide every handoff is one OUT and
        # one IN, so the count is the max of the two tick-gauge sums —
        # a pure prefill replica never ticks (nothing ever decodes
        # there), so only its decode-side restores reliably reach the
        # tick stream
        handoffs = max(
            sum(e.get("migrations_out", 0) for e in ticks),
            sum(e.get("migrations_in", 0) for e in ticks),
        )
        # goodput accounting (absent in pre-goodput streams): useful
        # tokens vs computed token lanes per tick window, plus the
        # host-computed serving MFU (window-weighted mean, so long
        # ticks count for what they cost)
        gticks = [e for e in ticks if e.get("useful_tokens") is not None]
        goodput = None
        if gticks:
            window = lambda e: ((e.get("tick_ms") or 0.0)
                                + (e.get("prefill_stall_ms") or 0.0))
            useful = sum(e["useful_tokens"] for e in gticks)
            wasted = sum(e.get("wasted_token_lanes", 0) for e in gticks)
            window_ms = sum(window(e) for e in gticks)
            mfu_ticks = [e for e in gticks
                         if e.get("serving_mfu") is not None]
            mfu_den = sum(window(e) for e in mfu_ticks)
            goodput = {
                "useful_tokens": useful,
                "wasted_token_lanes": wasted,
                "useful_fraction": (
                    round(useful / (useful + wasted), 4)
                    if useful + wasted else None
                ),
                "goodput_tokens_per_sec": (
                    round(useful / (window_ms / 1000), 1)
                    if window_ms else None
                ),
                "serving_mfu": (
                    round(sum(e["serving_mfu"] * window(e)
                              for e in mfu_ticks) / mfu_den, 6)
                    if mfu_den else None
                ),
            }
        # speculative-decoding gauges (absent unless a spec-enabled
        # engine wrote the stream): draft/accept totals and committed
        # tokens per verify launch — the launches-per-token headline
        # (docs/SERVING.md "Speculative decoding")
        spticks = [e for e in ticks if e.get("spec_drafted") is not None]
        speculation = None
        if spticks:
            drafted = sum(e["spec_drafted"] for e in spticks)
            accepted = sum(e.get("spec_accepted", 0) for e in spticks)
            sp_tokens = sum(e.get("tokens_emitted", 0) for e in spticks)
            # per STREAM per launch (a non-speculative tick would be
            # exactly 1.0); older records without spec_streams fall
            # back to the per-tick figure
            streams = sum(e.get("spec_streams") or 0 for e in spticks)
            speculation = {
                "ticks": len(spticks),
                "drafted": drafted,
                "accepted": accepted,
                "acceptance_rate": (
                    round(accepted / drafted, 4) if drafted else None
                ),
                "accepted_tokens_per_tick": round(
                    sp_tokens / (streams or len(spticks)), 2
                ),
            }
        # the lane ladder's gauges (absent from a stream written before
        # every tick carried its width): how many ticks
        # ran narrower than capacity and at what lane widths
        # (docs/SERVING.md "Occupancy-adaptive ticks")
        cticks = [e for e in ticks
                  if e.get("compaction_width") is not None]
        compaction = None
        if cticks:
            widths = [e["compaction_width"] for e in cticks]
            narrowed = [e for e in cticks
                        if e.get("capacity")
                        and e["compaction_width"] < e["capacity"]]
            compaction = {
                "ticks": len(cticks),
                "ticks_compacted": len(narrowed),
                "mean_width": round(sum(widths) / len(widths), 2),
                "min_width": min(widths),
            }
        # 3-D serving-mesh pipeline gauges (absent unless a stage>1
        # engine wrote the stream): stage width, ticks that ran the
        # explicit microbatched clock, and the warmup/drain bubble
        # lanes those schedules idled (docs/SERVING.md "3-D serving
        # mesh")
        pticks = [e for e in ticks
                  if e.get("stage_shards") is not None]
        pipeline = None
        if pticks:
            bubble = sum(e.get("bubble_lanes", 0) for e in pticks)
            pipeline = {
                "stage_shards": pticks[-1]["stage_shards"],
                "ticks": len(pticks),
                "pipelined_ticks": sum(
                    1 for e in pticks if e.get("bubble_lanes")),
                "bubble_lanes": bubble,
            }
        # quantized-serving gauges (absent unless an int8 engine wrote
        # the stream): the dtype stamp + resident-bytes from the last
        # stamped tick (docs/SERVING.md "Quantized serving")
        qticks = [e for e in ticks if e.get("quantized") is not None]
        memory = None
        if qticks:
            last = qticks[-1]
            memory = {
                "quantized": last["quantized"],
                "weight_bytes": last.get("weight_bytes"),
                "page_pool_bytes": last.get("page_pool_bytes"),
            }
        # multi-tenant LoRA gauges (absent unless a LoRA-serving engine
        # wrote the stream): adapter-cache churn totals, last residency
        # gauge and the per-tick distinct-adapter peak (docs/SERVING.md
        # "Multi-tenant LoRA")
        # durable-session gauges (absent unless a session-store engine
        # wrote the stream): park/resume/expire totals from the tick
        # windows, last tier-occupancy gauges, plus the background
        # sweeper's sessions_gc reap count (docs/SERVING.md "Durable
        # sessions")
        sticks = [e for e in ticks
                  if e.get("sessions_parked_host") is not None]
        sessions = None
        if sticks:
            last = sticks[-1]
            sessions = {
                "parked_host": last["sessions_parked_host"],
                "parked_disk": last.get("sessions_parked_disk"),
                "bytes_host": last.get("sessions_bytes_host"),
                "bytes_disk": last.get("sessions_bytes_disk"),
                "parks": sum(e.get("session_parks", 0) for e in sticks),
                "resumes": sum(
                    e.get("session_resumes", 0) for e in sticks),
                "expires": sum(
                    e.get("session_expires", 0) for e in sticks),
                "gc_sweeps": sum(
                    1 for e in events if e.get("kind") == "sessions_gc"),
                "gc_expired": sum(
                    e.get("expired", 0) for e in events
                    if e.get("kind") == "sessions_gc"),
            }
        aticks = [e for e in ticks
                  if e.get("adapters_resident") is not None]
        adapters = None
        if aticks:
            adapters = {
                "resident": aticks[-1]["adapters_resident"],
                "cache_hits": sum(
                    e.get("adapter_cache_hits", 0) for e in aticks),
                "cache_misses": sum(
                    e.get("adapter_cache_misses", 0) for e in aticks),
                "cache_evictions": sum(
                    e.get("adapter_cache_evictions", 0) for e in aticks),
                "peak_live": max(
                    e.get("adapters_live", 0) for e in aticks),
            }
        report["serving"] = {
            "ticks": len(ticks),
            "decode_tokens": tokens,
            "tick_ms": _pcts([e["tick_ms"] for e in ticks
                              if e.get("tick_ms") is not None]),
            "decode_tokens_per_sec": (
                round(tokens / (total_ms / 1000), 1) if total_ms else None
            ),
            "mean_slot_occupancy": (
                round(sum(ratios) / len(ratios), 4) if ratios else None
            ),
            "peak_queue_depth": max(e.get("queue_depth", 0) for e in ticks),
            "prefill_stall_ms": _pcts(stalls) if stalls else None,
            "prefill_chunk_tokens": chunk_tokens,
            "prefill_chunk_tokens_per_sec": (
                round(chunk_tokens / (chunk_total_ms / 1000), 1)
                if chunk_tokens and chunk_total_ms else None
            ),
            "goodput": goodput,
            "prefix_cache": prefix,
            "compaction": compaction,
            "pipeline": pipeline,
            "speculation": speculation,
            "adapters": adapters,
            "sessions": sessions,
            "preemptions": preemptions,
            "migrations": {"handoffs": handoffs} if handoffs else None,
            "kv_pages": kv_pages,
            "memory": memory,
        }

    # --- per-replica split (the data-parallel serving fabric): tick and
    # request records stamped with a "replica" id by the router's shared
    # stream.  Gauges per replica: queue depth, occupancy, free KV pages
    # (capacity - used; pure-SSM replicas have no page pool -> "-").
    rep_ticks = [e for e in ticks if e.get("replica") is not None]
    if rep_ticks:
        per: dict[int, dict] = {}
        for e in rep_ticks:
            d = per.setdefault(e["replica"], {
                "ticks": 0, "decode_tokens": 0, "occ": [], "queue": [],
                "kv_free": [],
            })
            d["ticks"] += 1
            d["decode_tokens"] += e.get("tokens_emitted", 0)
            if e.get("capacity"):
                d["occ"].append(e["occupied"] / e["capacity"])
            d["queue"].append(e.get("queue_depth", 0))
            if e.get("kv_pages_used") is not None:
                d["kv_free"].append(
                    (e.get("kv_pages_capacity") or 0) - e["kv_pages_used"]
                )
        req_by_rep: dict[int, int] = {}
        # per-replica ITL: each replica's request records carry
        # mergeable streaming histograms — merge them per replica AND
        # across the whole fabric, so the per-replica split and the
        # fabric-wide latency view come from the same bounded state
        itl_by_rep: dict[int, StreamingHistogram] = {}
        fabric_itl: StreamingHistogram | None = None
        for e in events:
            if e.get("kind") == "request" and e.get("replica") is not None:
                rid = e["replica"]
                req_by_rep[rid] = req_by_rep.get(rid, 0) + 1
                h = e.get("itl_hist")
                if h:
                    h = StreamingHistogram.from_dict(h)
                    if rid in itl_by_rep:
                        itl_by_rep[rid].merge(h)
                    else:
                        itl_by_rep[rid] = h
                    # the fabric view accumulates into its OWN (empty,
                    # same-geometry) histogram — seeding it with h would
                    # alias a per-replica view's state
                    if fabric_itl is None:
                        fabric_itl = StreamingHistogram(h.lo, h.hi,
                                                        h.growth)
                    fabric_itl.merge(h)
        report["replicas"] = {
            rid: {
                "ticks": d["ticks"],
                "requests": req_by_rep.get(rid, 0),
                "decode_tokens": d["decode_tokens"],
                "mean_occupancy": (
                    round(sum(d["occ"]) / len(d["occ"]), 4)
                    if d["occ"] else None
                ),
                "peak_queue_depth": max(d["queue"]) if d["queue"] else 0,
                "min_kv_free_pages": (
                    min(d["kv_free"]) if d["kv_free"] else None
                ),
                "itl_ms": (
                    itl_by_rep[rid].summary() if rid in itl_by_rep else None
                ),
            }
            for rid, d in sorted(per.items())
        }
        if fabric_itl is not None:
            report["fabric"] = {
                "requests": sum(req_by_rep.values()),
                "itl_ms": fabric_itl.summary(),
            }

    # --- per-request latency (the serving stream's "request" records)
    reqs = [e for e in events if e.get("kind") == "request"]
    if reqs:
        def col(key):
            return [e[key] for e in reqs if e.get(key) is not None]

        itl = None
        for e in reqs:
            h = e.get("itl_hist")
            if not h:
                continue
            h = StreamingHistogram.from_dict(h)
            itl = h if itl is None else itl.merge(h)
        finish: dict[str, int] = {}
        for e in reqs:
            reason = e.get("finish_reason") or "?"
            finish[reason] = finish.get(reason, 0) + 1
        report["requests"] = {
            "count": len(reqs),
            "finish_reasons": finish,
            "prompt_tokens": sum(col("prompt_tokens")),
            "new_tokens": sum(col("new_tokens")),
            "queue_wait_ms": _pcts(col("queue_wait_ms")),
            "ttft_ms": _pcts(col("ttft_ms")),
            "e2e_ms": _pcts(col("e2e_ms")),
            "itl_ms": itl.summary() if itl is not None else None,
        }
        # prefix-cache TTFT split: cache-enabled engines stamp each
        # request record with its admission outcome ("full"/"partial"/
        # None) — the hit-vs-miss TTFT gap is the cache's headline
        stamped = [e for e in reqs if "prefix_hit" in e]
        if stamped:
            report["requests"]["ttft_hit_ms"] = _pcts(
                [e["ttft_ms"] for e in stamped
                 if e["prefix_hit"] and e.get("ttft_ms") is not None])
            report["requests"]["ttft_miss_ms"] = _pcts(
                [e["ttft_ms"] for e in stamped
                 if not e["prefix_hit"] and e.get("ttft_ms") is not None])
        # disaggregated-tier migrations (docs/SERVING.md "Disaggregated
        # tiers"): migrated request records carry the handoff trail —
        # count, host latency, prefill-source -> decode-target replica
        # pair — rendered as its own table when any request migrated
        migrated = [e for e in reqs if e.get("migrations")]
        if migrated:
            routes: dict[str, int] = {}
            for e in migrated:
                pair = (f"{_fmt(e.get('migration_source'))}->"
                        f"{_fmt(e.get('replica'))}")
                routes[pair] = routes.get(pair, 0) + 1
            report["migrations"] = {
                "requests": len(migrated),
                "total_handoffs": sum(e["migrations"] for e in migrated),
                "migration_ms": _pcts(
                    [e["migration_ms"] for e in migrated
                     if e.get("migration_ms") is not None]),
                "ttft_ms": _pcts(
                    [e["ttft_ms"] for e in migrated
                     if e.get("ttft_ms") is not None]),
                "routes": dict(sorted(routes.items())),
            }

    # --- fabric health (serving_health records from the cross-host
    # service's HeartbeatMonitor, serving/service/health.py): per-
    # replica beat/miss counts, heartbeat round-trip percentiles, and
    # the lifecycle/failover timeline — the at-a-glance answer to "did
    # any worker die, and did its work land somewhere"
    health = [e for e in events if e.get("kind") == "serving_health"]
    if health:
        hper: dict[int, dict] = {}
        for e in health:
            d = hper.setdefault(e.get("replica"), {
                "beats": 0, "missed": 0, "failovers": 0,
                "failover_errors": 0, "requeued": 0,
                "heartbeat_ms": [], "transitions": [],
            })
            ev = e.get("event")
            if ev == "beat":
                d["beats"] += 1
                if e.get("heartbeat_ms") is not None:
                    d["heartbeat_ms"].append(e["heartbeat_ms"])
            elif ev == "missed":
                d["missed"] += 1
            elif ev == "failover":
                d["failovers"] += 1
                d["requeued"] += len(e.get("requeued") or [])
            elif ev == "failover_error":
                d["failover_errors"] += 1
            elif ev == "lifecycle":
                d["transitions"].append(e.get("transition"))
        report["fabric_health"] = {
            "replicas": {
                rid: {
                    "beats": d["beats"],
                    "missed": d["missed"],
                    "failovers": d["failovers"],
                    "failover_errors": d["failover_errors"],
                    "requeued": d["requeued"],
                    "heartbeat_ms": (_pcts(d["heartbeat_ms"])
                                     if d["heartbeat_ms"] else None),
                    "transitions": d["transitions"],
                }
                for rid, d in sorted(hper.items(),
                                     key=lambda kv: (kv[0] is None, kv[0]))
            }
        }

    # --- SLO attainment (obs/slo.py): the monitor stamps its targets
    # into the stream as an slo_config event, so attainment is
    # recomputable offline from the request records; breach/recovery
    # transitions are their own event records
    marks = [e for e in events if e.get("kind") == "event"]
    slo_cfgs = [e for e in marks if e.get("name") == "slo_config"]
    if slo_cfgs:
        cfg_ev = slo_cfgs[-1]
        breaches = [e for e in marks if e.get("name") == "slo_breach"]
        recoveries = [e for e in marks if e.get("name") == "slo_recovered"]
        metrics_out: dict[str, dict] = {}
        for metric in ("ttft_ms", "itl_ms", "queue_wait_ms"):
            target = cfg_ev.get(f"{metric}_p95_target")
            if not target:
                continue
            if metric == "itl_ms":
                # per-request judgement: the request's own ITL p95
                vals = []
                for e in reqs:
                    h = e.get("itl_hist")
                    if h and h.get("count"):
                        vals.append(
                            StreamingHistogram.from_dict(h).percentile(95)
                        )
            else:
                vals = [e[metric] for e in reqs
                        if e.get(metric) is not None]
            met = sum(1 for v in vals if v <= target)
            metrics_out[metric] = {
                "target_p95_ms": target,
                "requests": len(vals),
                "met": met,
                "attainment": (
                    round(met / len(vals), 4) if vals else None
                ),
                "breaches": sum(
                    1 for e in breaches if e.get("metric") == metric
                ),
            }
        report["slo"] = {
            "window": cfg_ev.get("window"),
            "metrics": metrics_out,
            # chronological, so list order IS the breach timeline
            # (breach -> recovered -> breach must not read as ended-
            # recovered)
            "breach_events": [
                {k: v for k, v in e.items() if k != "kind"}
                for e in sorted(breaches + recoveries,
                                key=lambda e: e.get("t_ms", 0.0))
            ],
        }

    # --- point events (divergence markers etc.)
    if marks:
        report["events"] = [
            {k: v for k, v in e.items() if k != "kind"} for e in marks
        ]
    return report


# ------------------------------------------------------------------ render


def _table(rows: list[list], header: list[str]) -> str:
    widths = [max(len(str(r[i])) for r in [header] + rows)
              for i in range(len(header))]
    fmt = "  ".join(f"{{:<{w}}}" for w in widths)
    lines = [fmt.format(*header), fmt.format(*("-" * w for w in widths))]
    lines += [fmt.format(*(str(c) for c in r)) for r in rows]
    return "\n".join(lines)


def _fmt(v) -> str:
    return "-" if v is None else str(v)


def _pct_row(name: str, p: dict) -> list:
    return [name, p["count"], _fmt(p["mean"]), _fmt(p["p50"]),
            _fmt(p["p95"]), _fmt(p["p99"]), _fmt(p["max"])]


def format_report(report: dict) -> str:
    out = []
    if "spans" in report:
        rows = [
            [name, d["count"], d["total_ms"], d["mean_ms"], d["max_ms"],
             "-" if d["share"] is None else f"{d['share'] * 100:.1f}%"]
            for name, d in report["spans"].items()
        ]
        out.append("== phase breakdown (spans) ==\n" + _table(
            rows, ["phase", "count", "total_ms", "mean_ms", "max_ms", "share"]
        ))
    if "train" in report:
        t = report["train"]
        head = (f"== train ==\nsteps: {t['steps']}   "
                f"loss: {_fmt(t['first_loss'])} -> {_fmt(t['last_loss'])}   "
                f"mean tok/s: {_fmt(t['mean_tokens_per_sec'])}")
        if t["non_finite_losses"]:
            head += f"   NON-FINITE LOSSES: {t['non_finite_losses']}"
        out.append(head + "\n" + _table(
            [_pct_row("step_ms", t["step_ms"])],
            ["metric", "count", "mean", "p50", "p95", "p99", "max"],
        ))
    if "val" in report:
        v = report["val"]
        out.append(f"== val ==\nevals: {v['count']}   "
                   f"last loss: {_fmt(v['last_loss'])}")
    if "serving" in report:
        s = report["serving"]
        head = (
            f"== serving ticks ==\nticks: {s['ticks']}   decode tokens: "
            f"{s['decode_tokens']}   decode tok/s: "
            f"{_fmt(s['decode_tokens_per_sec'])}   mean occupancy: "
            f"{_fmt(s['mean_slot_occupancy'])}   peak queue: "
            f"{s['peak_queue_depth']}"
        )
        if s.get("prefill_chunk_tokens"):
            head += (
                f"   prefill chunk tokens: {s['prefill_chunk_tokens']}"
                f" (dispatch tok/s: {_fmt(s['prefill_chunk_tokens_per_sec'])})"
            )
        if s.get("goodput"):
            g = s["goodput"]
            mfu = g["serving_mfu"]
            head += (
                f"\ngoodput: {g['useful_tokens']} useful tokens / "
                f"{g['wasted_token_lanes']} wasted lanes "
                f"(useful {_fmt(g['useful_fraction'])})   "
                f"goodput tok/s: {_fmt(g['goodput_tokens_per_sec'])}   "
                f"serving MFU: "
                f"{'-' if mfu is None else f'{mfu * 100:.2f}%'}"
            )
        if s.get("prefix_cache"):
            pc = s["prefix_cache"]
            rate = pc["hit_rate"]
            head += (
                f"\nprefix cache: {pc['hits']} hits / {pc['misses']} misses"
                f" ({'-' if rate is None else f'{rate * 100:.1f}%'})   "
                f"saved prefill tokens: {pc['saved_prefill_tokens']}   "
                f"entries: {_fmt(pc['entries'])}   "
                f"bytes: {_fmt(pc['bytes'])}"
            )
        if s.get("compaction"):
            c = s["compaction"]
            head += (
                f"\ncompaction: {c['ticks_compacted']}/{c['ticks']} "
                f"ticks compacted   mean lane width: {c['mean_width']}"
                f"   min: {c['min_width']}"
            )
        if s.get("pipeline"):
            p = s["pipeline"]
            head += (
                f"\npipeline: {p['stage_shards']} stages   "
                f"{p['pipelined_ticks']}/{p['ticks']} ticks microbatched"
                f"   bubble lanes: {_fmt(p['bubble_lanes'])}"
            )
        if s.get("speculation"):
            sp = s["speculation"]
            rate = sp["acceptance_rate"]
            head += (
                f"\nspeculation: {sp['accepted']} / {sp['drafted']} "
                f"drafts accepted "
                f"({'-' if rate is None else f'{rate * 100:.1f}%'})   "
                f"accepted tokens/tick: "
                f"{_fmt(sp['accepted_tokens_per_tick'])}"
            )
        if s.get("adapters"):
            a = s["adapters"]
            head += (
                f"\nadapters: {a['resident']} resident   cache "
                f"{a['cache_hits']} hits / {a['cache_misses']} misses / "
                f"{a['cache_evictions']} evictions   peak live/tick: "
                f"{a['peak_live']}"
            )
        if s.get("sessions"):
            se = s["sessions"]
            head += (
                f"\nsessions: {se['parked_host']} host / "
                f"{_fmt(se['parked_disk'])} disk parked   "
                f"{se['parks']} parks / {se['resumes']} resumes / "
                f"{se['expires']} expired   gc: {se['gc_sweeps']} sweeps "
                f"({se['gc_expired']} reaped)"
            )
        if s.get("preemptions"):
            head += f"\npreemptions: {s['preemptions']}"
        if s.get("migrations"):
            head += (f"\ntier migrations: "
                     f"{s['migrations']['handoffs']} prefill->decode "
                     f"handoff(s)")
        if s.get("kv_pages"):
            kv = s["kv_pages"]
            head += (
                f"\nkv pages: peak {kv['peak_used']}/{_fmt(kv['capacity'])}"
                f"   mean {kv['mean_used']}   allocs {kv['allocs']}"
                f"   frees {kv['frees']}"
            )
        if s.get("memory"):
            m = s["memory"]
            q = m["quantized"]
            head += (
                f"\nquantized: weights={q.get('weights')} "
                f"kv={q.get('kv')}   weight bytes: "
                f"{_fmt(m['weight_bytes'])}   page pool bytes: "
                f"{_fmt(m['page_pool_bytes'])}"
            )
        rows = [_pct_row("tick_ms", s["tick_ms"])]
        if s.get("prefill_stall_ms") is not None:
            rows.append(_pct_row("prefill_stall_ms", s["prefill_stall_ms"]))
        out.append(head + "\n" + _table(
            rows, ["metric", "count", "mean", "p50", "p95", "p99", "max"],
        ))
    if "replicas" in report:
        def _itl(d):
            itl = d.get("itl_ms")
            return ("-" if not itl
                    else f"{_fmt(itl['p50'])}/{_fmt(itl['p95'])}")

        rows = [
            [rid, d["requests"], d["ticks"], d["decode_tokens"],
             _fmt(d["mean_occupancy"]), d["peak_queue_depth"],
             _fmt(d["min_kv_free_pages"]), _itl(d)]
            for rid, d in report["replicas"].items()
        ]
        if "fabric" in report:
            f = report["fabric"]
            rows.append(["all", f["requests"], "-", "-", "-", "-", "-",
                         _itl(f)])
        out.append("== per-replica (serving fabric) ==\n" + _table(
            rows, ["replica", "requests", "ticks", "decode_tokens",
                   "mean_occ", "peak_queue", "min_kv_free",
                   "itl_p50/p95"]
        ))
    if "fabric_health" in report:
        rows = []
        for rid, d in report["fabric_health"]["replicas"].items():
            hb = d["heartbeat_ms"]
            rows.append([
                _fmt(rid), d["beats"], d["missed"], d["failovers"],
                d["requeued"],
                "-" if hb is None else f"{_fmt(hb['p50'])}/{_fmt(hb['p95'])}",
                ",".join(t for t in d["transitions"] if t) or "-",
            ])
        out.append("== fabric health (serving_health) ==\n" + _table(
            rows, ["replica", "beats", "missed", "failovers", "requeued",
                   "hb_p50/p95_ms", "transitions"]
        ))
    if "migrations" in report:
        m = report["migrations"]
        rows = [_pct_row("migration_ms", m["migration_ms"]),
                _pct_row("ttft_ms (migrated)", m["ttft_ms"])]
        routes = "   ".join(f"{pair}: {n}"
                            for pair, n in m["routes"].items())
        out.append(
            f"== migrations (disaggregated tiers) ==\n"
            f"migrated requests: {m['requests']}   handoffs: "
            f"{m['total_handoffs']}   routes (src->dst replica): "
            f"{routes}\n"
            + _table(rows,
                     ["metric", "count", "mean", "p50", "p95", "p99",
                      "max"])
        )
    if "slo" in report:
        s = report["slo"]
        rows = [
            [m, d["target_p95_ms"], d["requests"], d["met"],
             "-" if d["attainment"] is None
             else f"{d['attainment'] * 100:.1f}%",
             d["breaches"]]
            for m, d in s["metrics"].items()
        ]
        head = f"== SLO attainment (rolling window {_fmt(s['window'])}) =="
        out.append(head + "\n" + _table(
            rows, ["metric", "target_p95_ms", "requests", "met",
                   "attainment", "breaches"]
        ))
    if "requests" in report:
        r = report["requests"]
        rows = [_pct_row("queue_wait_ms", r["queue_wait_ms"]),
                _pct_row("ttft_ms", r["ttft_ms"]),
                _pct_row("e2e_ms", r["e2e_ms"])]
        if "ttft_hit_ms" in r:
            rows.append(_pct_row("ttft_ms (prefix hit)", r["ttft_hit_ms"]))
            rows.append(_pct_row("ttft_ms (miss)", r["ttft_miss_ms"]))
        if r["itl_ms"] is not None:
            rows.append(_pct_row("itl_ms", r["itl_ms"]))
        out.append(
            f"== request latency ==\nrequests: {r['count']}   "
            f"finish: {r['finish_reasons']}   prompt tokens: "
            f"{r['prompt_tokens']}   new tokens: {r['new_tokens']}\n"
            + _table(rows,
                     ["metric", "count", "mean", "p50", "p95", "p99", "max"])
        )
    if "events" in report:
        out.append("== events ==\n" + "\n".join(
            json.dumps(e) for e in report["events"]
        ))
    if not out:
        return "no recognizable telemetry records found"
    return "\n\n".join(out)


def _fetch_url(url: str, timeout_s: float = 10.0) -> str:
    import urllib.request

    with urllib.request.urlopen(url, timeout=timeout_s) as resp:
        return resp.read().decode("utf-8")


def build_live_report(base_url: str) -> dict:
    """One snapshot of a RUNNING fabric over HTTP — no file access:
    ``/metrics-summary`` (per-replica engine roll-ups), ``/healthz``
    (readiness + lifecycle states) and ``/metrics`` (the Prometheus
    exposition, parsed just enough to list the emitted families)."""
    base = base_url.rstrip("/")
    live: dict = {"url": base,
                  "replicas": json.loads(_fetch_url(base + "/metrics-summary"))}
    try:
        live["health"] = json.loads(_fetch_url(base + "/healthz"))
    except Exception as e:  # noqa: BLE001 — a 503 (not ready) still
        # carries the JSON body, but an old front end may lack the route
        import urllib.error

        if isinstance(e, urllib.error.HTTPError):
            live["health"] = json.loads(e.read().decode("utf-8"))
    try:
        from mamba_distributed_tpu.obs import prom

        fams = prom.parse_exposition(_fetch_url(base + "/metrics"))
        live["metric_families"] = sorted(fams)
    except Exception:  # noqa: BLE001 — pre-v5 front ends have no /metrics
        pass
    return live


def format_live_report(live: dict) -> str:
    out = [f"== live fabric @ {live['url']} =="]
    health = live.get("health") or {}
    if health:
        out.append(f"ready: {health.get('ready')}   "
                   f"pending: {health.get('pending')}   "
                   f"migrations: {health.get('migrations')}")
    rows = []
    for rid in sorted(live.get("replicas", {}), key=str):
        s = live["replicas"][rid] or {}
        hs = (health.get("replicas") or {}).get(str(rid), {})
        rows.append([rid, hs.get("state", "-"), s.get("ticks", 0),
                     s.get("decode_tokens", 0),
                     _fmt(s.get("decode_tokens_per_sec")),
                     _fmt(s.get("mean_tick_ms")),
                     s.get("finished_requests", 0),
                     _fmt((s.get("compile") or {}).get("compiles"))])
    if rows:
        out.append(_table(rows, ["replica", "state", "ticks", "tokens",
                                 "tok/s", "tick ms", "finished",
                                 "compiles"]))
    if live.get("metric_families"):
        out.append(f"/metrics families: {len(live['metric_families'])}")
    return "\n".join(out)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="phase-time breakdown + latency percentiles from the "
                    "repo's jsonl telemetry streams (docs/OBSERVABILITY.md)"
    )
    p.add_argument("files", nargs="*", help="jsonl stream(s): events.jsonl, "
                   "metrics.jsonl, serving jsonl — any mix")
    p.add_argument("--url", default=None, metavar="http://HOST:PORT",
                   help="report on a LIVE fabric instead of files: "
                        "fetches /metrics-summary, /healthz and /metrics "
                        "from the front end (no file access needed)")
    p.add_argument("--json", action="store_true",
                   help="emit the aggregated report as JSON instead of tables")
    args = p.parse_args(argv)
    if args.url is None and not args.files:
        p.error("either jsonl files or --url is required")
    if args.url:
        live = build_live_report(args.url)
        if args.json and not args.files:
            print(json.dumps({"live": live}, indent=1))
            return 0
        print(format_live_report(live))
        if not args.files:
            return 0
    report = build_report(load_events(args.files))
    if args.json:
        print(json.dumps(report, indent=1))
    else:
        print(format_report(report))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
