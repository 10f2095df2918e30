"""Metrics logging: reference-text-format log + structured jsonl.

``log.txt`` carries exactly the reference's 3-field lines
(``"{step} train {loss:.6f}"`` / ``"{step} val {loss:.4f}"``,
/root/reference/train.py:124,150,240) so its plot tooling (plot.ipynb)
parses ours unchanged.  ``metrics.jsonl`` carries the structured record
SURVEY.md §5 calls for — step, loss, lr, grad norm, step time,
tokens/sec, MFU — one JSON object per line, machine-parseable.  The
console line shows both worlds (the reference printed step/loss/lr/
norm/dt/tok-sec, train.py:237-239; MFU is new).
"""

from __future__ import annotations

import os

from mamba_distributed_tpu.obs.histogram import StreamingHistogram
from mamba_distributed_tpu.obs.tracer import append_jsonl


class MetricsLogger:
    def __init__(self, log_dir: str, master_process: bool = True,
                 filename: str = "log.txt",
                 jsonl_filename: str = "metrics.jsonl"):
        self.master = master_process
        self.log_file = None
        self.jsonl_file = None
        # truncation (reference train.py:122) is deferred to the first write
        # so a checkpoint resume can preserve the pre-crash history
        self._truncate_pending = True
        if master_process:
            os.makedirs(log_dir, exist_ok=True)
            self.log_file = os.path.join(log_dir, filename)
            self.jsonl_file = os.path.join(log_dir, jsonl_filename)

    def preserve_history(self) -> None:
        """Keep the existing log files (called on checkpoint resume)."""
        self._truncate_pending = False

    def _append(self, line: str, record: dict | None = None) -> None:
        if self.log_file:
            mode = "w" if self._truncate_pending else "a"
            if self._truncate_pending:
                # truncate BOTH files together so a record-less first write
                # can never leave a previous run's jsonl to interleave with
                open(self.jsonl_file, "w").close()
            self._truncate_pending = False
            with open(self.log_file, mode) as f:
                f.write(line + "\n")
            if record is not None:
                append_jsonl(self.jsonl_file, record)

    def train_step(self, step: int, loss: float, lr: float, grad_norm: float,
                   dt_s: float, tokens_per_sec: float,
                   mfu: float | None = None,
                   mfu_hw: float | None = None) -> None:
        """``mfu`` is the model-FLOPs convention (the judged one);
        ``mfu_hw`` additionally counts the chunked algorithm's extra
        arithmetic (utils/flops.py module docstring).  Both are None
        off a TPU — there is no peak to divide by — and the line and
        the record then carry no MFU at all."""
        if not self.master:
            return
        mfu_txt = "" if mfu is None else f" | mfu: {mfu * 100:.1f}%"
        print(
            f"step {step:5d} | loss: {loss:.6f} | lr {lr:.4e} | "
            f"norm: {grad_norm:.4f} | dt: {dt_s * 1000:.2f}ms | "
            f"tok/sec: {tokens_per_sec:.2f}{mfu_txt}"
        )
        record = {
            "step": step, "kind": "train", "loss": round(loss, 6),
            "lr": lr, "grad_norm": round(grad_norm, 4),
            "step_ms": round(dt_s * 1000, 2),
            "tokens_per_sec": round(tokens_per_sec, 1),
        }
        if mfu is not None:
            record["mfu"] = round(mfu, 4)
        if mfu_hw is not None:
            record["mfu_hw"] = round(mfu_hw, 4)
        self._append(f"{step} train {loss:.6f}", record)

    def val(self, step: int, loss: float) -> None:
        if not self.master:
            return
        print(f"validation loss: {loss:.4f}")
        self._append(
            f"{step} val {loss:.4f}",
            {"step": step, "kind": "val", "loss": round(loss, 4)},
        )


class ServingMetrics:
    """Serving-engine counters: queue depth, slot occupancy, throughput.

    The engine (serving/engine.py) calls ``record_prefill`` once per
    admission and ``record_tick`` once per compiled decode tick;
    ``summary()`` rolls everything up for whoever drives the engine
    (docs/OBSERVABILITY.md).  With ``jsonl_path`` set, every tick also
    appends one structured record — same one-JSON-object-per-line format
    as MetricsLogger's metrics.jsonl, tagged ``"kind": "serving_tick"``.

    Decode is weight-bandwidth-bound, so ``mean_slot_occupancy`` is the
    throughput model: each tick reads the full weights once regardless of
    how many slots are live, and every occupied slot rides that same read
    — batch-fill is (nearly) free aggregate tokens/sec (docs/SERVING.md).

    Per-request latency (the metrics that matter under real traffic:
    queue-wait, time-to-first-token, inter-token latency) aggregates in
    three streaming bounded-bucket histograms (obs/histogram.py) — p50/
    p95/p99 with fixed memory, no samples stored — rolled up under
    ``summary()["latency"]``.  The engine stamps the request lifecycle
    and calls ``record_queue_wait``/``record_ttft``/``record_itl``;
    ``record_request`` additionally appends one ``"kind": "request"``
    jsonl record per finished request when ``jsonl_path`` is set.

    ``replica`` (the data-parallel serving fabric, serving/router.py)
    stamps every serving_tick/request record with the owning replica's
    id, so one shared jsonl stream splits back into per-replica tables
    (scripts/obs_report.py renders queue depth, occupancy, and
    free-page gauges per replica).

    Goodput: every tick record also carries ``useful_tokens`` /
    ``wasted_token_lanes`` / ``goodput_tokens_per_sec`` /
    ``serving_mfu`` — raw tok/s with the static-shape waste (empty
    slot lanes, chunk padding) made visible, and a host-computed MFU
    from the analytic FLOPs rates the engine installs via
    ``configure_goodput`` (utils/flops.py "model" convention; no
    device counters).  ``summary()["goodput"]`` is the roll-up.
    """

    def __init__(self, capacity: int, jsonl_path: str | None = None,
                 replica: int | None = None):
        self.capacity = capacity
        self.jsonl_path = jsonl_path
        self.replica = replica
        self.ticks = 0
        self.decode_tokens = 0
        self.decode_time_s = 0.0
        self.prefills = 0
        self.prefill_tokens = 0
        self.prefill_time_s = 0.0
        # chunked prefill (serving/prefill.py): per-chunk dispatch counters
        # + the per-step prefill stall (host time the engine spends on
        # prefill work between two ticks — what chunking exists to bound)
        self.prefill_chunks = 0
        self.prefill_chunk_tokens = 0
        self.prefill_chunk_time_s = 0.0
        self.prefill_stall_s = 0.0
        self.prefill_stall_ms = StreamingHistogram()
        self._occupied_sum = 0
        self._queue_depth_sum = 0
        self.peak_queue_depth = 0
        # hybrid paged-KV gauges (serving/engine.py): last-seen pool
        # occupancy + cumulative allocator churn; None/0 until a hybrid
        # engine reports them
        self.kv_pages_used: int | None = None
        self.kv_pages_capacity: int | None = None
        self.kv_page_allocs = 0
        self.kv_page_frees = 0
        self.peak_kv_pages_used = 0
        self.finished_requests = 0
        # goodput accounting (serving/engine.py passes the lane counts):
        # useful tokens vs token lanes actually computed (padded slots +
        # chunk padding), plus host-computed serving MFU from the
        # analytic FLOPs rates configure_goodput() installs
        self.useful_tokens = 0
        self.computed_token_lanes = 0
        self._goodput_window_s = 0.0
        self._goodput_flops = 0.0
        self._fpt_decode: float | None = None
        self._fpt_prefill: float | None = None
        self._peak_flops: float | None = None
        self.queue_wait_ms = StreamingHistogram()
        self.ttft_ms = StreamingHistogram()
        self.itl_ms = StreamingHistogram()
        # expert layers (models/lm._moe_mlp; the engine reports a launch's
        # load once its fetch has returned): rows routed to the experts
        # held here, rows offered (served rows x top_k x layers), and each
        # launch's largest-over-mean load of a held expert
        self.expert_rows = 0
        self.expert_rows_offered = 0
        self.expert_load_max_over_mean = StreamingHistogram()
        # prefix-state cache (serving/prefix_cache.py): the engine calls
        # configure_prefix_cache() when the cache is on, unlocking the
        # summary()["prefix_cache"] section — hit-rate, saved prefill
        # tokens, and the TTFT split hit-vs-miss (the cache's headline)
        self._prefix_cache_on = False
        self.prefix_full_hits = 0
        self.prefix_partial_hits = 0
        self.prefix_misses = 0
        self.prefix_saved_tokens = 0
        self.prefix_ttft_hit_ms = StreamingHistogram()
        self.prefix_ttft_miss_ms = StreamingHistogram()
        # quantized serving (ops/quant.py; docs/SERVING.md "Quantized
        # serving"): the engine calls configure_memory() when either
        # weight or KV quantization is on, unlocking summary()["memory"]
        # — resident weight bytes, page-pool bytes and the dtype pair —
        # and the greedy-token-disagreement counter the divergence-
        # sentinel-backed parity checker (ops/quant.assert_stream_close)
        # bumps when a quantized stream drifts from its reference
        self._memory_on = False
        self.weight_bytes: int | None = None
        self.page_pool_bytes: int | None = None
        self.weight_dtype: str | None = None
        self.kv_dtype: str | None = None
        self.greedy_token_disagreements = 0
        # speculative decoding (serving/spec_decode.py): the engine
        # calls configure_speculation() when cfg.spec_tokens > 0,
        # unlocking summary()["speculation"] — draft/accept counters,
        # the per-tick acceptance-rate histogram and the headline
        # accepted-tokens-per-tick (committed tokens per full-model
        # launch; > 1 is the bandwidth win).  Off by default so K=0
        # summaries/records stay byte-stable.
        self._spec_on = False
        self.spec_tokens_cfg: int | None = None
        self.spec_drafter: str | None = None
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_stream_ticks = 0  # Σ live streams over verify ticks
        self.spec_accept_rate = StreamingHistogram(lo=1e-2, hi=200.0)
        # the decode tick's lane ladder (serving/engine.py;
        # docs/SERVING.md "Occupancy-adaptive ticks"): every tick record
        # carries the width it launched at, and summary()["compaction"]
        # rolls them up — per-width tick histogram, distinct narrow
        # widths used ("recompiles": each is one program), and the token
        # lanes the narrower launches saved vs static capacity.
        self.compaction_ticks = 0  # ticks that ran NARROWER than capacity
        self.compaction_hist: dict[int, int] = {}  # lane width -> ticks
        self.compaction_lanes_saved = 0
        # 3-D serving mesh pipeline axis (parallel/mesh.serving_mesh;
        # docs/SERVING.md "3-D serving mesh"): the engine calls
        # configure_pipeline() when serving_stage_shards > 1,
        # unlocking summary()["pipeline"] — stage width, how many
        # ticks ran the explicit microbatched clock, and the
        # warmup/drain bubble lanes those schedules idled (billed
        # into goodput's wasted_token_lanes).  Off by default so
        # stage=1 records/summaries stay byte-stable.
        self._pipeline_on = False
        self.stage_shards_cfg: int | None = None
        self.pipeline_ticks = 0  # ticks that ran the explicit clock
        self.pipeline_bubble_lanes = 0
        self._pipeline_slot_lanes = 0  # Σ slot_lanes on those ticks
        # multi-tenant LoRA serving (serving/adapters.py): the engine
        # calls configure_adapters() when cfg.lora_max_adapters > 0,
        # unlocking summary()["adapters"] — registry/cache shape,
        # cache hit/miss/eviction totals and the per-tick distinct-
        # adapter gauge.  Off by default so LoRA-less summaries and
        # records stay byte-stable.
        self._adapters_on = False
        self.lora_max_adapters: int | None = None
        self.lora_rank: int | None = None
        self.lora_cache_slots: int | None = None
        self.adapters_resident: int = 0
        self.adapter_cache_hits = 0
        self.adapter_cache_misses = 0
        self.adapter_cache_evictions = 0
        self.peak_adapters_live = 0
        self._adapters_live_sum = 0
        self._adapter_ticks = 0
        # priority preemptions (serving/engine.py swap-out/resume)
        self.preemptions = 0
        # online per-tenant adapter tuning plane (serving/tuning/ plus
        # the engine's fairness quota and mid-stream hot swaps): the
        # owner calls configure_tuning() when any of those features is
        # live — tenant_max_slots > 0 at engine construction, or
        # lazily on the first hot swap / tune job — unlocking
        # summary()["tuning"].  Off by default so tuning-less
        # summaries and records stay byte-stable.
        self._tuning_on = False
        self.tenant_quota_stalls = 0
        self.adapter_hot_swaps = 0
        self.tune_jobs_submitted = 0
        self.tune_jobs_completed = 0
        self.tune_jobs_failed = 0
        self.tune_train_steps = 0
        self.tune_deploys = 0
        self.tune_yields = 0
        self.tune_step_ms = StreamingHistogram()
        self.tune_last_loss: float | None = None
        # durable sessions (serving/sessions/store.py): the engine
        # calls configure_sessions() when a session store is attached,
        # unlocking summary()["sessions"] — park/resume/expire totals,
        # the per-resume restore latency histogram and the last-seen
        # tier gauges (host entries/bytes vs disk entries/bytes).  Off
        # by default so store-less summaries/records stay byte-stable.
        self._sessions_on = False
        self.session_parks = 0
        self.session_resumes = 0
        self.session_expires = 0
        self.session_resume_ms = StreamingHistogram()
        self.sessions_parked_host: int | None = None
        self.sessions_parked_disk: int | None = None
        self.sessions_bytes_host: int | None = None
        self.sessions_bytes_disk: int | None = None
        # admission-control load shedding (serving/autoscale/
        # admission.py): the owner calls configure_admission() when an
        # AdmissionController is installed, unlocking
        # summary()["admission"] — total sheds split by reason.  Off by
        # default so admission-less summaries stay byte-stable.
        self._admission_on = False
        self.sheds = 0
        self.sheds_cap = 0
        self.sheds_deadline = 0
        # disaggregated prefill/decode handoffs (docs/SERVING.md
        # "Disaggregated tiers"): migrations OUT of this engine (a
        # prefill replica exporting its finished carry) vs IN (a
        # decode replica restoring one), with the per-handoff host
        # latency (packaging + restore dispatch)
        self.migrations_out = 0
        self.migrations_in = 0
        self.migration_ms = StreamingHistogram()
        # XLA compile watchdog (obs/watchdog.py): the engine calls
        # configure_compile() when a watchdog is attached, unlocking
        # summary()["compile"] and the per-tick `compiles`/`compile_ms`
        # stamps.  Off by default so watchdog-less records/summaries
        # stay byte-stable.
        self._compile_on = False
        self.compiles = 0
        self.compile_ms_total = 0.0
        # same deferred-truncation contract as MetricsLogger/SpanTracer:
        # a reused path starts fresh on the first write unless
        # preserve_history() ran, so two runs can never interleave
        self._truncate_pending = True

    def preserve_history(self) -> None:
        """Keep an existing jsonl stream (append instead of truncating)."""
        self._truncate_pending = False

    def configure_goodput(self, flops_per_decode_token: float,
                          flops_per_prefill_token: float,
                          peak_flops: float | None) -> None:
        """Install the analytic FLOPs rates (utils/flops.py, "model"
        convention — no device counters involved) that turn each tick's
        useful-token counts into a host-computed ``serving_mfu``.  The
        engine calls this once at construction; unconfigured metrics —
        and engines off a TPU, which pass ``peak_flops=None`` — still
        emit the goodput token fields with ``serving_mfu=None``."""
        self._fpt_decode = flops_per_decode_token
        self._fpt_prefill = flops_per_prefill_token
        self._peak_flops = peak_flops

    def _write_jsonl(self, record: dict) -> None:
        append_jsonl(self.jsonl_path, record, truncate=self._truncate_pending)
        self._truncate_pending = False

    def record_prefill(self, prompt_tokens: int, dt_s: float) -> None:
        """``dt_s`` is host dispatch time: prefill runs async and the next
        tick's token fetch absorbs device completion (serving/engine.py),
        so on an async backend the derived ``prefill_tokens_per_sec`` is
        a dispatch rate — an upper bound on device prefill throughput,
        not a measurement of it."""
        self.prefills += 1
        self.prefill_tokens += prompt_tokens
        self.prefill_time_s += dt_s

    def record_prefill_chunk(self, chunk_tokens: int, dt_s: float) -> None:
        """One chunked-prefill step (serving/prefill.py): ``chunk_tokens``
        of prompt dispatched in ``dt_s`` host seconds.  The whole prompt
        still gets one ``record_prefill`` at completion, so
        ``prefill_tokens_per_sec`` keeps its meaning; the chunk counters
        give the chunk-level dispatch throughput."""
        self.prefill_chunks += 1
        self.prefill_chunk_tokens += chunk_tokens
        self.prefill_chunk_time_s += dt_s

    def record_expert_load(self, load, rows_offered: int) -> dict:
        """One launch (a decode tick, a prefill chunk) of a model with
        expert layers, as ``models/lm._moe_mlp`` counts it over the
        launch's sub-steps and layers: ``load[e]`` rows reached held expert
        ``e``, and last the held experts reached; of ``rows_offered``
        (served rows x top_k x layers).  Returns the launch's figures under
        the names its span carries them."""
        load, hits = load[:-1], int(load[-1])
        rows, held = int(load.sum()), len(load)
        self.expert_rows += rows
        self.expert_rows_offered += rows_offered
        out = {"expert_rows": rows, "expert_hits": hits,
               "expert_rows_share": rows / rows_offered if rows_offered
               else None,
               "expert_load_max_over_mean":
                   float(load.max()) * held / rows if rows else None}
        if rows:
            self.expert_load_max_over_mean.record(
                out["expert_load_max_over_mean"])
        return out

    def record_prefill_stall(self, dt_s: float) -> None:
        """Host seconds one engine step spent on prefill work (admissions
        + chunk budget) before its tick — the stall chunking bounds."""
        self.prefill_stall_s += dt_s
        self.prefill_stall_ms.record(dt_s * 1000)

    # -------------------------------------------- prefix cache + preemption

    def configure_prefix_cache(self) -> None:
        """Mark the prefix-state cache live (engine construction):
        ``summary()`` gains its ``prefix_cache`` section."""
        self._prefix_cache_on = True

    def record_prefix_lookup(self, kind: str | None,
                             saved_tokens: int = 0) -> None:
        """One admission-time cache lookup: ``kind`` is "full" (prefill
        skipped outright), "partial" (seeded at a chunk boundary) or
        None (miss); ``saved_tokens`` the prompt tokens the hit's
        snapshot covers — prefill work NOT recomputed."""
        if kind == "full":
            self.prefix_full_hits += 1
        elif kind == "partial":
            self.prefix_partial_hits += 1
        else:
            self.prefix_misses += 1
        self.prefix_saved_tokens += saved_tokens

    def record_prefix_ttft(self, dt_s: float, hit: bool) -> None:
        """TTFT of a finished-prefill request, split by cache outcome —
        the delta between the two histograms is what the cache buys."""
        (self.prefix_ttft_hit_ms if hit
         else self.prefix_ttft_miss_ms).record(dt_s * 1000)

    def record_preemption(self) -> None:
        """One priority swap-out (serving/engine._preempt)."""
        self.preemptions += 1

    # ------------------------------------------------ speculative decoding

    def configure_speculation(self, spec_tokens: int, drafter: str) -> None:
        """Mark speculative decoding live (engine construction):
        ``summary()`` gains its ``speculation`` section and tick
        records their ``spec_drafted``/``spec_accepted`` stamps."""
        self._spec_on = True
        self.spec_tokens_cfg = spec_tokens
        self.spec_drafter = drafter

    # ---------------------------------------------- multi-tenant LoRA

    def configure_adapters(self, max_adapters: int, rank: int,
                           cache_slots: int) -> None:
        """Mark multi-tenant LoRA serving live (engine construction):
        ``summary()`` gains its ``adapters`` section and tick records
        their adapter-cache stamps."""
        self._adapters_on = True
        self.lora_max_adapters = int(max_adapters)
        self.lora_rank = int(rank)
        self.lora_cache_slots = int(cache_slots)

    # ------------------------------------------- online adapter tuning

    def configure_tuning(self) -> None:
        """Mark the online-tuning plane live: ``summary()`` gains its
        ``tuning`` section and tick records their quota-stall /
        hot-swap stamps.  Idempotent; the ``record_*`` methods below
        call it lazily, so the section appears exactly when the first
        tuning-plane event happens (byte-stable until then)."""
        self._tuning_on = True

    def record_quota_stall(self) -> None:
        """One admission deferred by the per-tenant fairness quota
        (serving/scheduler.TenantQuotaExceeded — requeued, not shed)."""
        self.configure_tuning()
        self.tenant_quota_stalls += 1

    def record_hot_swap(self) -> None:
        """One live stream switched adapter versions mid-flight
        (serving/engine.hot_swap_adapter)."""
        self.configure_tuning()
        self.adapter_hot_swaps += 1

    def record_tune_job(self, state: str,
                        job: dict | None = None) -> None:
        """One tune-job lifecycle transition: ``state`` is "submitted",
        "completed" or "failed" (serving/tuning/jobs.py).  ``job`` is
        the job's status dict; with a jsonl stream configured it lands
        as one ``"kind": "tune_job"`` record per transition (the
        docs/OBSERVABILITY.md event schema)."""
        self.configure_tuning()
        if state == "submitted":
            self.tune_jobs_submitted += 1
        elif state == "completed":
            self.tune_jobs_completed += 1
        else:
            self.tune_jobs_failed += 1
        if self.jsonl_path and job is not None:
            rec = {"kind": "tune_job", **job}
            if self.replica is not None:
                rec.setdefault("replica", self.replica)
            self._write_jsonl(rec)

    def record_tune_step(self, dt_ms: float,
                         loss: float | None = None) -> None:
        """One masked LoRA train step on a trainer-role replica:
        host wall ms and (when finite) the step's mean loss."""
        self.configure_tuning()
        self.tune_train_steps += 1
        self.tune_step_ms.record(dt_ms)
        if loss is not None:
            self.tune_last_loss = float(loss)

    def record_tune_deploy(self) -> None:
        """One converged job's ``name@v(N+1)`` hot-registered
        fabric-wide (serving/tuning/jobs.py deploy)."""
        self.configure_tuning()
        self.tune_deploys += 1

    def record_tune_yield(self) -> None:
        """One training slice skipped because serving pressure (SLO
        breach / queue depth) reclaimed the lane."""
        self.configure_tuning()
        self.tune_yields += 1

    # --------------------------------------------------- quantized serving

    def configure_memory(self, weight_bytes: int, page_pool_bytes: int,
                         weight_dtype: str, kv_dtype: str) -> None:
        """Install the resident-bytes gauges (engine construction, only
        when quantization is on — ``summary()["memory"]`` stays None and
        tick records byte-stable otherwise)."""
        self._memory_on = True
        self.weight_bytes = int(weight_bytes)
        self.page_pool_bytes = int(page_pool_bytes)
        self.weight_dtype = weight_dtype
        self.kv_dtype = kv_dtype

    # ------------------------------------------- pipeline (3-D mesh)

    def configure_pipeline(self, stage_shards: int) -> None:
        """Mark the serving mesh's pipeline ``stage`` axis live (engine
        construction, only at ``serving_stage_shards > 1``):
        ``summary()`` gains its ``pipeline`` section and tick records
        their ``stage_shards``/``bubble_lanes`` stamps."""
        self._pipeline_on = True
        self.stage_shards_cfg = int(stage_shards)

    # ----------------------------------------------- compile watchdog

    def configure_compile(self) -> None:
        """Mark the XLA compile watchdog live (engine construction):
        ``summary()`` gains its ``compile`` block and tick records
        their ``compiles``/``compile_ms`` stamps."""
        self._compile_on = True

    def record_greedy_disagreement(self, n: int = 1) -> None:
        """``n`` greedy tokens on which a quantized stream disagreed
        with its reference (fed by ops/quant.assert_stream_close — the
        divergence sentinels keep the flight-recorder side)."""
        self.greedy_token_disagreements += n

    def record_migration_out(self) -> None:
        """One prefill-complete carry exported to another replica
        (serving/engine._migrate_ready on a prefill-tier engine)."""
        self.migrations_out += 1

    def record_migration_in(self, dt_ms: float) -> None:
        """One migration artifact restored into a slot here
        (serving/engine._resume); ``dt_ms`` is the handoff's host
        latency — source-side packaging + this restore's dispatch."""
        self.migrations_in += 1
        self.migration_ms.record(dt_ms)

    # ---------------------------------------------------- durable sessions

    def configure_sessions(self) -> None:
        """Mark the durable session store live (engine construction):
        ``summary()`` gains its ``sessions`` section and tick records
        their session stamps (docs/SERVING.md "Durable sessions")."""
        self._sessions_on = True

    def record_session_park(self) -> None:
        """One live stream serialized into the session store (explicit
        ``park()`` or the admission valve's pressure park)."""
        self.session_parks += 1

    def record_session_resume(self, dt_ms: float) -> None:
        """One parked session restored into a slot here; ``dt_ms`` is
        the restore's host latency (store read + decode + dispatch)."""
        self.session_resumes += 1
        self.session_resume_ms.record(dt_ms)

    def record_session_expire(self, n: int = 1) -> None:
        """``n`` parked sessions reaped by the TTL sweeper."""
        self.session_expires += n

    # ------------------------------------------------- admission shedding

    def configure_admission(self) -> None:
        """Mark admission control live (AdmissionController
        construction): ``summary()`` gains its ``admission`` section
        (docs/SERVING.md "Elastic fabric")."""
        self._admission_on = True

    def record_shed(self, reason: str) -> None:
        """One request shed at the front door; ``reason`` is the
        ``AdmissionRejected`` reason ("queue_cap" | "queue_deadline")."""
        self.sheds += 1
        if reason == "queue_cap":
            self.sheds_cap += 1
        else:
            self.sheds_deadline += 1

    # ------------------------------------------------- per-request latency

    def record_queue_wait(self, dt_s: float) -> None:
        """Submit -> slot granted (admission)."""
        self.queue_wait_ms.record(dt_s * 1000)

    def record_ttft(self, dt_s: float) -> None:
        """Submit -> first generated token on the host."""
        self.ttft_ms.record(dt_s * 1000)

    def record_itl(self, dt_s: float, n: int = 1) -> None:
        """``n`` inter-token gaps of ``dt_s`` each (tokens that arrive in
        one tick share the tick's per-token average — the host can't see
        finer than its own sync points)."""
        self.itl_ms.record(dt_s * 1000, n)

    def record_request(self, record: dict) -> None:
        """One finished request: count it and append its jsonl record
        (``"kind": "request"``) when a stream is configured."""
        self.finished_requests += 1
        if self.jsonl_path:
            rec = {"kind": "request", **record}
            if self.replica is not None:
                rec.setdefault("replica", self.replica)
            self._write_jsonl(rec)

    def record_tick(
        self, occupied: int, queue_depth: int, tokens_emitted: int,
        dt_s: float, prefill_stall_ms: float = 0.0,
        prefill_chunk_tokens: int = 0, prefill_chunk_ms: float = 0.0,
        prefill_real_tokens: int = 0,
        prefill_oneshot_tokens: int = 0, prefill_oneshot_lanes: int = 0,
        slot_lanes: int = 0,
        traces: list | None = None,
        model_shards: int | None = None,
        stage_shards: int | None = None,
        bubble_lanes: int | None = None,
        preemptions: int = 0,
        migrations_out: int = 0,
        migrations_in: int = 0,
        tenant_quota_stalls: int = 0,
        adapter_hot_swaps: int = 0,
        prefix_hits: int | None = None,
        prefix_misses: int | None = None,
        prefix_saved_tokens: int | None = None,
        prefix_cache_entries: int | None = None,
        prefix_cache_bytes: int | None = None,
        kv_pages_used: int | None = None,
        kv_pages_capacity: int | None = None,
        kv_page_allocs: int = 0, kv_page_frees: int = 0,
        quantized: dict | None = None,
        weight_bytes: int | None = None,
        page_pool_bytes: int | None = None,
        spec_drafted: int | None = None,
        spec_accepted: int | None = None,
        spec_streams: int | None = None,
        compaction_width: int | None = None,
        adapters_resident: int | None = None,
        adapter_cache_hits: int = 0,
        adapter_cache_misses: int = 0,
        adapter_cache_evictions: int = 0,
        adapters_live: int = 0,
        sessions_parked_host: int | None = None,
        sessions_parked_disk: int | None = None,
        sessions_bytes_host: int | None = None,
        sessions_bytes_disk: int | None = None,
        session_parks: int = 0,
        session_resumes: int = 0,
        session_expires: int = 0,
        compiles: int | None = None,
        compile_ms: float = 0.0,
    ) -> None:
        """``prefill_stall_ms`` is the host time spent on prefill work
        since the PREVIOUS tick record (an engine step whose slots are
        all still mid-prefill runs no tick, so its work rolls into the
        next tick's record — the jsonl stream never drops any);
        ``prefill_chunk_tokens``/``prefill_chunk_ms`` are the chunked-
        prefill tokens dispatched in that window and their dispatch
        time, ``prefill_real_tokens`` the non-pad subset (the chunk-
        padding half of the goodput waste accounting);
        ``prefill_oneshot_tokens``/``prefill_oneshot_lanes`` the same
        real-vs-computed pair for UNCHUNKED admissions in the window
        (real prompt tokens vs the pow2-padded bucket lanes the
        one-shot prefill ran), so goodput/MFU stay comparable across
        the chunking threshold.  ``slot_lanes``
        is the token lanes the compiled tick computed (capacity x
        sub-steps — live or not, the static shape runs them all); with
        the emitted/real counts it yields the per-tick goodput fields:
        ``useful_tokens``, ``wasted_token_lanes``,
        ``goodput_tokens_per_sec`` (useful work over the tick + its
        prefill window) and ``serving_mfu`` (analytic FLOPs of the
        useful tokens over peak — see ``configure_goodput``).
        ``traces`` is the live request trace-id set, stamped into the
        record so host-side attribution can apportion ``tick_ms`` and
        FLOPs across resident requests (obs/context.py).
        ``model_shards`` (tensor-parallel serving engines, i.e. > 1)
        stamps the mesh's model-axis width on the record so per-tick
        rates are attributable to their weight layout; None (the
        replicated default) leaves the record unchanged.
        ``stage_shards`` (3-D pipelined serving engines, i.e. > 1)
        stamps the mesh's stage-axis width the same way, and
        ``bubble_lanes`` bills the explicit microbatched schedule's
        warmup/drain ramp — full-depth lane equivalents the pipeline
        idled this tick, 0 on GSPMD-fallback ticks — into the goodput
        lane count, so ``wasted_token_lanes`` is honest about the
        bubble; None (stage=1) leaves records byte-stable.
        ``prefix_hits``/``prefix_misses``/``prefix_saved_tokens`` are
        the prefix-state cache's window counters and
        ``prefix_cache_entries``/``prefix_cache_bytes`` its occupancy
        gauges — stamped only by cache-enabled engines (None leaves
        the record byte-stable), all host-side.  ``preemptions``
        counts priority swap-outs in the window (stamped only when
        nonzero).
        ``migrations_out``/``migrations_in`` count disaggregated-tier
        handoffs exported/restored in the window (stamped only when
        nonzero; docs/SERVING.md "Disaggregated tiers").
        ``kv_pages_used``/``kv_pages_capacity`` (hybrid paged-KV
        engines) gauge the page pool at this tick, with
        ``kv_page_allocs``/``kv_page_frees`` the allocator churn in the
        window — rendered by scripts/obs_report.py.
        ``quantized`` (int8 serving only — None keeps records
        byte-stable) is the ``{"weights": dtype, "kv": dtype}`` stamp,
        with ``weight_bytes``/``page_pool_bytes`` the resident-bytes
        gauges behind the capacity story (docs/SERVING.md "Quantized
        serving")."""
        self.ticks += 1
        self.decode_tokens += tokens_emitted
        self.decode_time_s += dt_s
        self._occupied_sum += occupied
        self._queue_depth_sum += queue_depth
        self.peak_queue_depth = max(self.peak_queue_depth, queue_depth)
        # --- goodput: useful tokens vs computed lanes over the window
        # (the tick plus the prefill work attributed to it)
        window_s = dt_s + prefill_stall_ms / 1000.0
        useful = (tokens_emitted + prefill_real_tokens
                  + prefill_oneshot_tokens)
        lanes = (slot_lanes + prefill_chunk_tokens
                 + prefill_oneshot_lanes + (bubble_lanes or 0))
        self.useful_tokens += useful
        self.computed_token_lanes += lanes
        self._goodput_window_s += window_s
        mfu = None
        if self._fpt_decode is not None and self._peak_flops and window_s > 0:
            flops = (tokens_emitted * self._fpt_decode
                     + (prefill_real_tokens + prefill_oneshot_tokens)
                     * self._fpt_prefill)
            self._goodput_flops += flops
            mfu = flops / (window_s * self._peak_flops)
        record = {
            "kind": "serving_tick", "tick": self.ticks,
            "occupied": occupied, "capacity": self.capacity,
            **({} if self.replica is None else {"replica": self.replica}),
            "queue_depth": queue_depth,
            "tokens_emitted": tokens_emitted,
            "tick_ms": round(dt_s * 1000, 3),
            "prefill_stall_ms": round(prefill_stall_ms, 3),
            "prefill_chunk_tokens": prefill_chunk_tokens,
            "prefill_chunk_ms": round(prefill_chunk_ms, 3),
            "prefill_oneshot_tokens": prefill_oneshot_tokens,
            "useful_tokens": useful,
            "wasted_token_lanes": max(lanes - useful, 0),
            "goodput_tokens_per_sec": (
                round(useful / window_s, 1) if window_s > 0 else None
            ),
            "serving_mfu": None if mfu is None else round(mfu, 6),
        }
        if traces is not None:
            record["traces"] = list(traces)
        if model_shards is not None:
            record["model_shards"] = model_shards
        if stage_shards is not None:
            # pipeline-axis stamps (only at stage > 1 — 2-D engines'
            # records stay byte-stable): the stage width and this
            # tick's bubble bill (0 when GSPMD ran the layer scan
            # without the explicit microbatch clock)
            record["stage_shards"] = stage_shards
            record["bubble_lanes"] = bubble_lanes or 0
            if bubble_lanes:
                self.pipeline_ticks += 1
                self.pipeline_bubble_lanes += bubble_lanes
                self._pipeline_slot_lanes += slot_lanes
        if preemptions:
            record["preemptions"] = preemptions
        if tenant_quota_stalls:
            # fairness-quota deferrals in the window (stamped only when
            # nonzero — quota-off engines' records stay byte-stable;
            # the cumulative total rides record_quota_stall)
            record["tenant_quota_stalls"] = tenant_quota_stalls
        if adapter_hot_swaps:
            # mid-stream adapter version swaps in the window (stamped
            # only when nonzero — swap-free records stay byte-stable)
            record["adapter_hot_swaps"] = adapter_hot_swaps
        if migrations_out:
            # disaggregated-tier handoffs in the window (stamped only
            # when live, so non-disagg streams stay byte-stable)
            record["migrations_out"] = migrations_out
        if migrations_in:
            record["migrations_in"] = migrations_in
        if prefix_hits is not None:
            record.update({
                "prefix_hits": prefix_hits,
                "prefix_misses": prefix_misses,
                "prefix_saved_tokens": prefix_saved_tokens,
                "prefix_cache_entries": prefix_cache_entries,
                "prefix_cache_bytes": prefix_cache_bytes,
            })
        if kv_pages_used is not None:
            self.kv_pages_used = kv_pages_used
            self.kv_pages_capacity = kv_pages_capacity
            self.kv_page_allocs += kv_page_allocs
            self.kv_page_frees += kv_page_frees
            self.peak_kv_pages_used = max(
                self.peak_kv_pages_used, kv_pages_used
            )
            record.update({
                "kv_pages_used": kv_pages_used,
                "kv_pages_capacity": kv_pages_capacity,
                "kv_page_allocs": kv_page_allocs,
                "kv_page_frees": kv_page_frees,
            })
        if quantized is not None:
            record["quantized"] = quantized
            record["weight_bytes"] = weight_bytes
            if page_pool_bytes is not None:
                record["page_pool_bytes"] = page_pool_bytes
        if spec_drafted is not None:
            # speculative-decoding window counters (stamped only when
            # speculation is on — K=0 records stay byte-stable): draft
            # lanes fed to the verify step and how many verified.  The
            # acceptance-rate histogram records the window's rate in
            # PERCENT (0-100) so the geometric buckets resolve it.
            self.spec_drafted += spec_drafted
            self.spec_accepted += spec_accepted or 0
            if spec_drafted:
                self.spec_accept_rate.record(
                    100.0 * (spec_accepted or 0) / spec_drafted
                )
            self.spec_stream_ticks += spec_streams or 0
            record["spec_drafted"] = spec_drafted
            record["spec_accepted"] = spec_accepted
            record["spec_streams"] = spec_streams
        if adapters_resident is not None:
            # multi-tenant LoRA gauges (stamped only when LoRA serving
            # is on — records stay byte-stable otherwise): cache
            # residency, this window's hit/miss/eviction churn, and
            # how many DISTINCT adapters this tick's one launch mixed
            self.adapters_resident = adapters_resident
            self.adapter_cache_hits += adapter_cache_hits
            self.adapter_cache_misses += adapter_cache_misses
            self.adapter_cache_evictions += adapter_cache_evictions
            self.peak_adapters_live = max(self.peak_adapters_live,
                                          adapters_live)
            self._adapters_live_sum += adapters_live
            self._adapter_ticks += 1
            record.update({
                "adapters_resident": adapters_resident,
                "adapter_cache_hits": adapter_cache_hits,
                "adapter_cache_misses": adapter_cache_misses,
                "adapter_cache_evictions": adapter_cache_evictions,
                "adapters_live": adapters_live,
            })
        if sessions_parked_host is not None:
            # durable-session gauges (stamped only when a session
            # store is attached — store-less records stay byte-stable):
            # tier occupancy at this tick plus this window's
            # park/resume/expire churn
            self.sessions_parked_host = sessions_parked_host
            self.sessions_parked_disk = sessions_parked_disk
            self.sessions_bytes_host = sessions_bytes_host
            self.sessions_bytes_disk = sessions_bytes_disk
            record.update({
                "sessions_parked_host": sessions_parked_host,
                "sessions_parked_disk": sessions_parked_disk,
                "sessions_bytes_host": sessions_bytes_host,
                "sessions_bytes_disk": sessions_bytes_disk,
                "session_parks": session_parks,
                "session_resumes": session_resumes,
                "session_expires": session_expires,
            })
        if compiles is not None:
            # compile-watchdog window counters (stamped only when a
            # watchdog is attached — records stay byte-stable
            # otherwise): XLA backend compiles observed since the
            # previous tick record and their wall ms.  A steady-state
            # engine stamps 0/0.0; anything persistently nonzero is
            # recompile thrash the watchdog's window event names.
            self.compiles += compiles
            self.compile_ms_total += compile_ms
            record["compiles"] = compiles
            record["compile_ms"] = round(compile_ms, 3)
        if compaction_width is not None:
            # the lane width this tick's launch computed (the engine
            # stamps it on every tick).  slot_lanes above is already
            # billed at that width, so the goodput fields price the
            # launch, not static capacity; lanes_saved is the delta a
            # full-width launch would have burned on the same tick.
            record["compaction_width"] = compaction_width
            self.compaction_hist[compaction_width] = (
                self.compaction_hist.get(compaction_width, 0) + 1
            )
            if compaction_width < self.capacity:
                self.compaction_ticks += 1
                self.compaction_lanes_saved += (
                    slot_lanes * self.capacity // compaction_width
                    - slot_lanes
                )
        if self.jsonl_path:
            self._write_jsonl(record)

    def summary(self) -> dict:
        return {
            "ticks": self.ticks,
            "decode_tokens": self.decode_tokens,
            "decode_tokens_per_sec": (
                round(self.decode_tokens / self.decode_time_s, 1)
                if self.decode_time_s else None
            ),
            "mean_tick_ms": (
                round(self.decode_time_s / self.ticks * 1000, 3)
                if self.ticks else None
            ),
            "mean_slot_occupancy": (
                round(self._occupied_sum / (self.ticks * self.capacity), 4)
                if self.ticks else 0.0
            ),
            "mean_queue_depth": (
                round(self._queue_depth_sum / self.ticks, 2) if self.ticks else 0.0
            ),
            "peak_queue_depth": self.peak_queue_depth,
            "prefills": self.prefills,
            "prefill_tokens": self.prefill_tokens,
            "prefill_time_s": round(self.prefill_time_s, 4),
            "prefill_tokens_per_sec": (
                round(self.prefill_tokens / self.prefill_time_s, 1)
                if self.prefill_time_s else None
            ),
            "prefill_chunks": self.prefill_chunks,
            "prefill_chunk_tokens": self.prefill_chunk_tokens,
            "prefill_chunk_tokens_per_sec": (
                round(self.prefill_chunk_tokens / self.prefill_chunk_time_s, 1)
                if self.prefill_chunk_time_s else None
            ),
            "prefill_stall_s": round(self.prefill_stall_s, 4),
            "prefill_stall_ms": self.prefill_stall_ms.summary(),
            "finished_requests": self.finished_requests,
            "preemptions": self.preemptions,
            "migrations": {
                "out": self.migrations_out,
                "in": self.migrations_in,
                "migration_ms": self.migration_ms.summary(),
            },
            "prefix_cache": (None if not self._prefix_cache_on else {
                "full_hits": self.prefix_full_hits,
                "partial_hits": self.prefix_partial_hits,
                "misses": self.prefix_misses,
                "hit_rate": (
                    round((self.prefix_full_hits + self.prefix_partial_hits)
                          / (self.prefix_full_hits + self.prefix_partial_hits
                             + self.prefix_misses), 4)
                    if (self.prefix_full_hits + self.prefix_partial_hits
                        + self.prefix_misses) else None
                ),
                "saved_prefill_tokens": self.prefix_saved_tokens,
                "ttft_hit_ms": self.prefix_ttft_hit_ms.summary(),
                "ttft_miss_ms": self.prefix_ttft_miss_ms.summary(),
            }),
            "goodput": {
                "useful_tokens": self.useful_tokens,
                "wasted_token_lanes": max(
                    self.computed_token_lanes - self.useful_tokens, 0
                ),
                "useful_fraction": (
                    round(self.useful_tokens / self.computed_token_lanes, 4)
                    if self.computed_token_lanes else None
                ),
                "goodput_tokens_per_sec": (
                    round(self.useful_tokens / self._goodput_window_s, 1)
                    if self._goodput_window_s else None
                ),
                "serving_mfu": (
                    round(self._goodput_flops
                          / (self._goodput_window_s * self._peak_flops), 6)
                    if (self._peak_flops and self._goodput_window_s
                        and self._fpt_decode is not None) else None
                ),
            },
            "compaction": {
                "ticks_compacted": self.compaction_ticks,
                # one tick program per distinct NARROW width ever used
                "recompiles": sum(1 for w in self.compaction_hist
                                  if w < self.capacity),
                "bucket_histogram": {
                    str(w): n
                    for w, n in sorted(self.compaction_hist.items())
                },
                "lanes_saved": self.compaction_lanes_saved,
            },
            "pipeline": (None if not self._pipeline_on else {
                "stage_shards": self.stage_shards_cfg,
                # ticks that ran the explicit microbatched clock (the
                # rest fell back to the GSPMD layer scan — same bits,
                # no ramp) and the ramp's cumulative idle lanes
                "pipelined_ticks": self.pipeline_ticks,
                "bubble_lanes": self.pipeline_bubble_lanes,
                "bubble_fraction": (
                    round(self.pipeline_bubble_lanes
                          / (self.pipeline_bubble_lanes
                             + self._pipeline_slot_lanes), 4)
                    if (self.pipeline_bubble_lanes
                        + self._pipeline_slot_lanes) else None
                ),
            }),
            "speculation": (None if not self._spec_on else {
                "spec_tokens": self.spec_tokens_cfg,
                "drafter": self.spec_drafter,
                "drafted": self.spec_drafted,
                "accepted": self.spec_accepted,
                "acceptance_rate": (
                    round(self.spec_accepted / self.spec_drafted, 4)
                    if self.spec_drafted else None
                ),
                "acceptance_rate_pct_hist":
                    self.spec_accept_rate.summary(),
                # committed tokens per STREAM per full-model launch —
                # the launches-per-token headline inverted (> 1.5 is
                # the bench gate on the repetitive-suffix workload; a
                # non-speculative tick is pinned at exactly 1.0)
                "accepted_tokens_per_tick": (
                    round(self.decode_tokens / self.spec_stream_ticks, 2)
                    if self.spec_stream_ticks else None
                ),
            }),
            "adapters": (None if not self._adapters_on else {
                "max_adapters": self.lora_max_adapters,
                "rank": self.lora_rank,
                "cache_slots": self.lora_cache_slots,
                "resident": self.adapters_resident,
                "cache_hits": self.adapter_cache_hits,
                "cache_misses": self.adapter_cache_misses,
                "cache_evictions": self.adapter_cache_evictions,
                "peak_live": self.peak_adapters_live,
                "mean_live": (
                    round(self._adapters_live_sum
                          / self._adapter_ticks, 2)
                    if self._adapter_ticks else None
                ),
            }),
            "tuning": (None if not self._tuning_on else {
                "quota_stalls": self.tenant_quota_stalls,
                "hot_swaps": self.adapter_hot_swaps,
                "jobs_submitted": self.tune_jobs_submitted,
                "jobs_completed": self.tune_jobs_completed,
                "jobs_failed": self.tune_jobs_failed,
                "train_steps": self.tune_train_steps,
                "deploys": self.tune_deploys,
                "yields": self.tune_yields,
                "step_ms": self.tune_step_ms.summary(),
                "last_loss": self.tune_last_loss,
            }),
            "admission": (None if not self._admission_on else {
                "sheds": self.sheds,
                "sheds_cap": self.sheds_cap,
                "sheds_deadline": self.sheds_deadline,
            }),
            "sessions": (None if not self._sessions_on else {
                "parked_host": self.sessions_parked_host,
                "parked_disk": self.sessions_parked_disk,
                "bytes_host": self.sessions_bytes_host,
                "bytes_disk": self.sessions_bytes_disk,
                "parks": self.session_parks,
                "resumes": self.session_resumes,
                "expires": self.session_expires,
                "resume_ms": self.session_resume_ms.summary(),
            }),
            "memory": (None if not self._memory_on else {
                "weight_bytes": self.weight_bytes,
                "page_pool_bytes": self.page_pool_bytes,
                "weight_dtype": self.weight_dtype,
                "kv_dtype": self.kv_dtype,
                "greedy_token_disagreements":
                    self.greedy_token_disagreements,
            }),
            "kv_pages": (
                None if self.kv_pages_used is None else {
                    "used": self.kv_pages_used,
                    "capacity": self.kv_pages_capacity,
                    "peak_used": self.peak_kv_pages_used,
                    "allocs": self.kv_page_allocs,
                    "frees": self.kv_page_frees,
                }
            ),
            "experts": (None if not self.expert_rows_offered else {
                "rows": self.expert_rows,
                "rows_offered": self.expert_rows_offered,
                "rows_share": self.expert_rows / self.expert_rows_offered,
                "load_max_over_mean":
                    self.expert_load_max_over_mean.summary(),
            }),
            "compile": (None if not self._compile_on else {
                "compiles": self.compiles,
                "compile_ms": round(self.compile_ms_total, 3),
            }),
            "latency": {
                "queue_wait_ms": self.queue_wait_ms.summary(),
                "ttft_ms": self.ttft_ms.summary(),
                "itl_ms": self.itl_ms.summary(),
            },
        }

    def histogram_dicts(self) -> dict:
        """Full sparse bucket forms of the latency histograms
        (``StreamingHistogram.to_dict``) — what the Prometheus
        exposition needs (``summary()`` carries only the p50/p95/p99
        roll-ups; bucket lines need the counts).  Shipped next to the
        summary in the worker ``summary`` RPC payload."""
        out = {
            "queue_wait_ms": self.queue_wait_ms.to_dict(),
            "ttft_ms": self.ttft_ms.to_dict(),
            "itl_ms": self.itl_ms.to_dict(),
        }
        if self._tuning_on:
            # gated like summary()["tuning"]: a tuning-less fabric's
            # exposition stays byte-identical (no empty histogram)
            out["tune_step_ms"] = self.tune_step_ms.to_dict()
        return out
