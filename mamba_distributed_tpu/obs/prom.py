"""Prometheus text exposition for the serving fabric — stdlib only.

The fabric's live telemetry plane (docs/OBSERVABILITY.md "Live
telemetry plane") is a pull surface: ``GET /metrics`` on the HTTP
front end renders every replica's ``ServingMetrics`` roll-up — plus
the controller's own fabric gauges — in the Prometheus text format
(version 0.0.4), one scrape target for the whole fabric.  A worker
can additionally expose itself directly (``scripts/serve_worker.py
--metrics-port``) so per-host scrapers keep working when the front
end is down.

Three layers, all pure functions over plain dicts so the wire payload
(`summary` RPC: summary + full histogram dicts + live stats) renders
without touching engine objects:

- ``MetricFamily`` + ``render()``: the exposition encoder.  Counters,
  gauges and histograms; label values escaped per the format spec
  (``\\``, ``\"``, ``\n``); histogram buckets are CUMULATIVE with a
  terminal ``+Inf`` bucket and the ``_sum``/``_count`` pair, derived
  from ``StreamingHistogram.to_dict()``'s sparse geometric counts.
- ``replica_families()`` / ``fabric_families()``: the fabric's metric
  schema — every name emitted here must appear in the
  docs/OBSERVABILITY.md metric table (``scripts/check_metrics_schema.py``
  is the drift gate: names, not numbers).
- ``parse_exposition()``: a minimal parser for the same format —
  enough for the round-trip unit tests and the schema gate; not a
  general Prometheus client.

Counters here are process-lifetime totals re-read from each replica's
metrics object at scrape time (the Prometheus counter contract:
monotonic within one worker boot; a worker restart resets them, which
scrapers detect as a counter reset).
"""

from __future__ import annotations

import math

CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"

# metric name prefix shared by every family the fabric emits
PREFIX = "mamba_"

_VALID_TYPES = ("counter", "gauge", "histogram")


def escape_label_value(value) -> str:
    """Escape a label value per the text-format spec: backslash, double
    quote and newline are the only escaped characters."""
    return (str(value).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _format_value(value) -> str:
    if value is None:
        return "NaN"
    if isinstance(value, bool):
        return "1" if value else "0"
    if isinstance(value, int):
        return str(value)
    v = float(value)
    if math.isnan(v):
        return "NaN"
    if math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(v)


def _format_sample(name: str, labels: dict, value) -> str:
    if labels:
        body = ",".join(
            f'{k}="{escape_label_value(v)}"' for k, v in labels.items()
        )
        return f"{name}{{{body}}} {_format_value(value)}"
    return f"{name} {_format_value(value)}"


class MetricFamily:
    """One named metric family: a type, a help line, N labeled samples."""

    def __init__(self, name: str, mtype: str, help: str):
        if mtype not in _VALID_TYPES:
            raise ValueError(f"metric type must be one of {_VALID_TYPES}, "
                             f"got {mtype!r}")
        self.name = name
        self.mtype = mtype
        self.help = help
        # list of (suffix, labels, value): suffix "" for plain samples,
        # "_bucket"/"_sum"/"_count" for histogram series
        self.samples: list[tuple[str, dict, object]] = []

    def add(self, value, **labels) -> "MetricFamily":
        """Add one sample (counters/gauges)."""
        if self.mtype == "histogram":
            raise ValueError(f"{self.name} is a histogram; use "
                             f"add_histogram()")
        self.samples.append(("", labels, value))
        return self

    def add_histogram(self, hist: dict, **labels) -> "MetricFamily":
        """Add one histogram from ``StreamingHistogram.to_dict()`` form.

        Buckets are emitted cumulatively at the geometric upper edges
        that actually hold counts, closed by the mandatory ``+Inf``
        bucket — sparse but valid: any quantile estimate over the
        emitted edges matches one over the full edge set because the
        omitted buckets hold zero observations.
        """
        if self.mtype != "histogram":
            raise ValueError(f"{self.name} is a {self.mtype}; "
                             f"add_histogram() needs a histogram family")
        lo = float(hist["lo"])
        growth = float(hist["growth"])
        hi = float(hist["hi"])
        n_buckets = int(math.ceil(math.log(hi / lo) / math.log(growth)))
        counts = {int(i): int(c) for i, c in hist.get("counts", {}).items()}
        cum = 0
        for index in sorted(counts):
            cum += counts[index]
            if index == 0:
                le = lo
            elif index >= n_buckets + 1:
                le = math.inf  # overflow bucket only closes at +Inf
            else:
                le = lo * growth ** index
            if math.isinf(le):
                continue  # folded into the terminal +Inf bucket below
            self.samples.append(
                ("_bucket", {**labels, "le": _format_value(le)}, cum))
        total = int(hist.get("count", 0))
        self.samples.append(("_bucket", {**labels, "le": "+Inf"}, total))
        self.samples.append(("_sum", dict(labels), float(hist.get("total",
                                                                  0.0))))
        self.samples.append(("_count", dict(labels), total))
        return self


def render(families: list[MetricFamily]) -> str:
    """Render families to one exposition document (trailing newline)."""
    lines: list[str] = []
    for fam in families:
        if not fam.samples:
            continue
        lines.append(f"# HELP {fam.name} {fam.help}")
        lines.append(f"# TYPE {fam.name} {fam.mtype}")
        for suffix, labels, value in fam.samples:
            lines.append(_format_sample(fam.name + suffix, labels, value))
    return "\n".join(lines) + "\n"


# --------------------------------------------------------------------------
# the fabric's metric schema
# --------------------------------------------------------------------------

def _fam(name, mtype, help) -> MetricFamily:
    return MetricFamily(PREFIX + name, mtype, help)


def replica_families(snapshots: list[dict]) -> list[MetricFamily]:
    """Render per-replica snapshots into the replica-level families.

    Each snapshot: ``{"replica": id, "role": str, "summary": dict,
    "histograms": {name: to_dict()}, "stats": dict}`` — exactly the
    worker ``summary`` RPC payload plus the controller's replica/role
    labels.  Missing feature blocks (``kv_pages`` None, no compile
    watchdog, no histograms shipped) simply emit nothing — the same
    off-means-absent contract the jsonl records keep.
    """
    ticks = _fam("ticks_total", "counter", "Engine ticks executed.")
    dtok = _fam("decode_tokens_total", "counter",
                "Decode tokens sampled (all slots).")
    tps = _fam("decode_tokens_per_sec", "gauge",
               "Decode tokens per wall second over the metrics window.")
    tickms = _fam("tick_ms_mean", "gauge", "Mean engine tick wall ms.")
    occ = _fam("slot_occupancy", "gauge",
               "Mean fraction of slots occupied per tick.")
    qdepth = _fam("queue_depth", "gauge",
                  "Requests queued (admitted, not yet resident).")
    resident = _fam("slots_resident", "gauge", "Slots currently resident.")
    cap = _fam("slot_capacity", "gauge", "Slot capacity S.")
    fin = _fam("finished_requests_total", "counter",
               "Requests finished (all finish reasons).")
    preempt = _fam("preemptions_total", "counter",
                   "Priority preemptions (slot evicted to host RAM).")
    mig_out = _fam("migrations_out_total", "counter",
                   "Streams migrated off this replica.")
    mig_in = _fam("migrations_in_total", "counter",
                  "Streams migrated onto this replica.")
    kv_used = _fam("kv_pages_used", "gauge", "Hybrid KV pages in use.")
    kv_cap = _fam("kv_pages_capacity", "gauge", "Hybrid KV page capacity.")
    kv_peak = _fam("kv_pages_peak_used", "gauge",
                   "Peak hybrid KV pages in use.")
    kv_allocs = _fam("kv_page_allocs_total", "counter",
                     "Hybrid KV page allocations.")
    kv_frees = _fam("kv_page_frees_total", "counter",
                    "Hybrid KV page frees.")
    useful = _fam("goodput_useful_fraction", "gauge",
                  "Useful fraction of computed token lanes.")
    gtps = _fam("goodput_tokens_per_sec", "gauge",
                "Useful tokens per wall second.")
    mfu = _fam("serving_mfu", "gauge",
               "Model FLOPs utilization of the serving window.")
    compiles = _fam("compiles_total", "counter",
                    "XLA backend compiles observed by the watchdog.")
    compile_ms = _fam("compile_ms_total", "counter",
                      "Wall ms spent in XLA backend compiles.")
    # online adapter tuning (serving/tuning/): None-gated on
    # summary()["tuning"] exactly like the kv/compile blocks — a fabric
    # with no tuning plane renders byte-identically to before
    quota_stalls = _fam("tenant_quota_stalls_total", "counter",
                        "Admissions deferred by the per-tenant "
                        "fairness quota (requeued, not shed).")
    hot_swaps = _fam("adapter_hot_swaps_total", "counter",
                     "Live streams switched adapter versions "
                     "mid-flight (carry invalidated once).")
    tune_jobs = _fam("tune_jobs_total", "counter",
                     "Tune-job lifecycle transitions, by state "
                     "(submitted/completed/failed).")
    tune_steps = _fam("tune_train_steps_total", "counter",
                      "Masked LoRA train steps run on trainer lanes.")
    tune_deploys = _fam("tune_deploys_total", "counter",
                        "Converged adapter versions hot-registered "
                        "fabric-wide.")
    tune_yields = _fam("tune_yields_total", "counter",
                       "Training slices yielded to serving pressure "
                       "(SLO breach).")
    tune_loss = _fam("tune_last_loss", "gauge",
                     "Most recent tune step's mean loss.")
    hists = {
        "queue_wait_ms": _fam("queue_wait_ms", "histogram",
                              "Per-request queue wait (admission to "
                              "slot), ms."),
        "ttft_ms": _fam("ttft_ms", "histogram",
                        "Per-request time to first token, ms."),
        "itl_ms": _fam("itl_ms", "histogram",
                       "Per-request inter-token latency, ms."),
        "tune_step_ms": _fam("tune_step_ms", "histogram",
                             "Per-step LoRA train wall time, ms "
                             "(shipped only when tuning is live)."),
    }
    for snap in snapshots:
        if not snap:
            continue
        labels = {"replica": snap.get("replica"),
                  "role": snap.get("role", "mixed")}
        s = snap.get("summary") or {}
        ticks.add(s.get("ticks", 0), **labels)
        dtok.add(s.get("decode_tokens", 0), **labels)
        if s.get("decode_tokens_per_sec") is not None:
            tps.add(s["decode_tokens_per_sec"], **labels)
        if s.get("mean_tick_ms") is not None:
            tickms.add(s["mean_tick_ms"], **labels)
        if s.get("mean_slot_occupancy") is not None:
            occ.add(s["mean_slot_occupancy"], **labels)
        fin.add(s.get("finished_requests", 0), **labels)
        preempt.add(s.get("preemptions", 0), **labels)
        mig = s.get("migrations") or {}
        mig_out.add(mig.get("out", 0), **labels)
        mig_in.add(mig.get("in", 0), **labels)
        stats = snap.get("stats") or {}
        if stats.get("depth") is not None:
            qdepth.add(stats["depth"], **labels)
        elif s.get("mean_queue_depth") is not None:
            qdepth.add(s["mean_queue_depth"], **labels)
        if stats.get("resident") is not None:
            resident.add(stats["resident"], **labels)
        if stats.get("capacity") is not None:
            cap.add(stats["capacity"], **labels)
        kv = s.get("kv_pages")
        if kv:
            kv_used.add(kv.get("used", 0), **labels)
            kv_cap.add(kv.get("capacity", 0), **labels)
            kv_peak.add(kv.get("peak_used", 0), **labels)
            kv_allocs.add(kv.get("allocs", 0), **labels)
            kv_frees.add(kv.get("frees", 0), **labels)
        good = s.get("goodput") or {}
        if good.get("useful_fraction") is not None:
            useful.add(good["useful_fraction"], **labels)
        if good.get("goodput_tokens_per_sec") is not None:
            gtps.add(good["goodput_tokens_per_sec"], **labels)
        if good.get("serving_mfu") is not None:
            mfu.add(good["serving_mfu"], **labels)
        comp = s.get("compile")
        if comp:
            compiles.add(comp.get("compiles", 0), **labels)
            compile_ms.add(comp.get("compile_ms", 0.0), **labels)
        tun = s.get("tuning")
        if tun:
            quota_stalls.add(tun.get("quota_stalls", 0), **labels)
            hot_swaps.add(tun.get("hot_swaps", 0), **labels)
            for state in ("submitted", "completed", "failed"):
                tune_jobs.add(tun.get(f"jobs_{state}", 0),
                              **labels, state=state)
            tune_steps.add(tun.get("train_steps", 0), **labels)
            tune_deploys.add(tun.get("deploys", 0), **labels)
            tune_yields.add(tun.get("yields", 0), **labels)
            if tun.get("last_loss") is not None:
                tune_loss.add(tun["last_loss"], **labels)
        for key, fam in hists.items():
            h = (snap.get("histograms") or {}).get(key)
            if h:
                fam.add_histogram(h, **labels)
    return [ticks, dtok, tps, tickms, occ, qdepth, resident, cap, fin,
            preempt, mig_out, mig_in, kv_used, kv_cap, kv_peak, kv_allocs,
            kv_frees, useful, gtps, mfu, compiles, compile_ms,
            quota_stalls, hot_swaps, tune_jobs, tune_steps, tune_deploys,
            tune_yields, tune_loss, *hists.values()]


def fabric_families(*, replicas: int, accepting: int, ready: bool,
                    obs_records_pulled: int | None = None,
                    obs_records_dropped: int | None = None,
                    queue_depth: int | None = None,
                    sheds: dict | None = None,
                    autoscale: dict | None = None,
                    tune_queue_depth: int | None = None
                    ) -> list[MetricFamily]:
    """The controller's own fabric-level gauges (no replica label).
    ``queue_depth``/``sheds``/``autoscale`` are None-gated like the obs
    counters: a fabric without admission control or an autoscaler
    renders byte-identically to the pre-elastic exposition."""
    fams = [
        _fam("fabric_replicas", "gauge",
             "Replicas registered with the router.").add(replicas),
        _fam("fabric_replicas_accepting", "gauge",
             "Replicas currently accepting work.").add(accepting),
        _fam("fabric_ready", "gauge",
             "1 when at least one replica accepts work "
             "(the /healthz readiness bit).").add(1 if ready else 0),
    ]
    if obs_records_pulled is not None:
        fams.append(_fam("fabric_obs_records_pulled_total", "counter",
                         "Span/event records drained off worker obs "
                         "rings.").add(obs_records_pulled))
    if obs_records_dropped is not None:
        fams.append(_fam("fabric_obs_records_dropped_total", "counter",
                         "Ring records that aged out before a pull "
                         "(cursor gaps).").add(obs_records_dropped))
    if queue_depth is not None:
        fams.append(_fam("fabric_queue_depth", "gauge",
                         "Queued-but-unstarted requests fabric-wide "
                         "(what the admission cap bounds).")
                    .add(queue_depth))
    if sheds is not None:
        fam = _fam("fabric_admission_sheds_total", "counter",
                   "Requests shed at the front door, by reason "
                   "(AdmissionRejected -> HTTP 429).")
        for reason in ("queue_cap", "queue_deadline"):
            fam.add(sheds.get(reason, 0), reason=reason)
        fams.append(fam)
    if autoscale is not None:
        fams += [
            _fam("fabric_autoscale_scale_ups_total", "counter",
                 "Replicas live-attached by the autoscaler.")
            .add(autoscale.get("scale_ups", 0)),
            _fam("fabric_autoscale_scale_downs_total", "counter",
                 "Replicas drained for retirement by the autoscaler.")
            .add(autoscale.get("scale_downs", 0)),
        ]
    if tune_queue_depth is not None:
        fams.append(_fam("fabric_tune_queue_depth", "gauge",
                         "Unfinished tune jobs (active + queued) on "
                         "the fabric's tuning plane.")
                    .add(tune_queue_depth))
    return fams


def render_fabric(snapshots: list[dict], **fabric_kw) -> str:
    """One fabric-wide exposition document: fabric gauges + replicas."""
    return render(fabric_families(**fabric_kw) + replica_families(snapshots))


# --------------------------------------------------------------------------
# minimal parser (tests + scripts/check_metrics_schema.py)
# --------------------------------------------------------------------------

def _unescape_label_value(raw: str) -> str:
    out, i = [], 0
    while i < len(raw):
        c = raw[i]
        if c == "\\" and i + 1 < len(raw):
            nxt = raw[i + 1]
            out.append({"\\": "\\", '"': '"', "n": "\n"}.get(nxt, nxt))
            i += 2
        else:
            out.append(c)
            i += 1
    return "".join(out)


def _parse_labels(body: str) -> dict:
    labels, i = {}, 0
    while i < len(body):
        if body[i] in ", ":
            i += 1
            continue
        eq = body.index("=", i)
        key = body[i:eq].strip()
        if body[eq + 1] != '"':
            raise ValueError(f"unquoted label value at {body[eq:]!r}")
        j = eq + 2
        raw = []
        while body[j] != '"':
            if body[j] == "\\":
                raw.append(body[j:j + 2])
                j += 2
            else:
                raw.append(body[j])
                j += 1
        labels[key] = _unescape_label_value("".join(raw))
        i = j + 1
    return labels


def _parse_value(raw: str) -> float:
    if raw == "+Inf":
        return math.inf
    if raw == "-Inf":
        return -math.inf
    return float(raw)


def parse_exposition(text: str) -> dict:
    """Parse an exposition document into families.

    Returns ``{family_name: {"type": str, "help": str, "samples":
    [(sample_name, labels_dict, value), ...]}}`` — histogram series
    (``_bucket``/``_sum``/``_count``) group under their base family.
    Strict enough to round-trip everything ``render()`` emits; raises
    ValueError on lines it cannot parse (the schema gate wants loud
    failure, not silent omission).
    """
    families: dict[str, dict] = {}
    types: dict[str, str] = {}

    def family_of(sample_name: str) -> str:
        for suffix in ("_bucket", "_sum", "_count"):
            if sample_name.endswith(suffix):
                base = sample_name[: -len(suffix)]
                if types.get(base) == "histogram":
                    return base
        return sample_name

    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        if line.startswith("# HELP "):
            name, _, help_text = line[len("# HELP "):].partition(" ")
            families.setdefault(
                name, {"type": None, "help": "", "samples": []}
            )["help"] = help_text
            continue
        if line.startswith("# TYPE "):
            name, _, mtype = line[len("# TYPE "):].partition(" ")
            mtype = mtype.strip()
            if mtype not in _VALID_TYPES:
                raise ValueError(f"unknown metric type {mtype!r} for "
                                 f"{name}")
            families.setdefault(
                name, {"type": None, "help": "", "samples": []}
            )["type"] = mtype
            types[name] = mtype
            continue
        if line.startswith("#"):
            continue  # other comments are legal
        if "{" in line:
            name = line[: line.index("{")]
            rest = line[line.index("{") + 1:]
            close = rest.rindex("}")
            labels = _parse_labels(rest[:close])
            value = _parse_value(rest[close + 1:].strip())
        else:
            name, _, raw = line.partition(" ")
            labels = {}
            value = _parse_value(raw.strip())
        fam = family_of(name)
        families.setdefault(
            fam, {"type": None, "help": "", "samples": []}
        )["samples"].append((name, labels, value))
    return families
