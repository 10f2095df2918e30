"""Speculative decoding tests (serving/spec_decode.py; ISSUE 12).

The contract under test:

  * LOSSLESS — greedy (top_k=1) speculative engine streams are
    token-identical to non-speculative greedy streams, whatever the
    drafter proposes: across mamba1/mamba2/hybrid, chunked long
    prompts, the (2,2) tensor-parallel serving mesh, prefix-cache warm
    hits and disaggregated prefill->decode migration — and
    ``generate()``'s speculative path matches the engine's by
    construction.  (Pinned at fp32 compute, the repo's tiny-config
    parity standard: under bf16 the chunk-vs-step rounding can flip a
    rare near-tie argmax — docs/SERVING.md "Speculative decoding".)
  * ROLLBACK — a rejected tick restores the pre-tick conv/SSM carries
    bit-exactly and leaves every LIVE KV page cell untouched (written
    draft cells past ``lengths`` are dead by contract), including when
    pages were recycled from an evicted request (the alias case).
  * NO RETRACE — the verify/commit steps run at one static shape per
    engine: TRACE_COUNTS stay flat across accept/reject/occupancy
    mixes once warm.
  * K=0 IS OFF — spec_tokens=0 engines carry no drafter, stamp no
    spec fields on records, and keep the exact pre-spec behavior.

Runnable standalone: ``pytest tests/test_spec_decode.py`` (the ``spec``
marker selects this surface).
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mamba_distributed_tpu.config import ModelConfig
from mamba_distributed_tpu.inference import generate
from mamba_distributed_tpu.models import init_lm_params
from mamba_distributed_tpu.serving import (
    GenerationRequest,
    ModelDrafter,
    NGramDrafter,
    RequestRouter,
    ServingEngine,
)
from mamba_distributed_tpu.serving import spec_decode
from mamba_distributed_tpu.utils.metrics import ServingMetrics

pytestmark = [pytest.mark.spec, pytest.mark.serving, pytest.mark.fast]

CHUNK = 16
K = 3  # draft tokens; verify width K+1


def tiny_cfg(layer="mamba2", **kw):
    kw.setdefault("prefill_chunk_tokens", CHUNK)
    kw.setdefault("prefill_tokens_per_tick", CHUNK)
    return ModelConfig(d_model=32, n_layer=2, vocab_size=64, ssm_layer=layer,
                       headdim=8, chunk_size=16, d_state=16,
                       compute_dtype="float32", **kw)


def hybrid_cfg(**kw):
    return tiny_cfg(attn_layer_idx=(1,), attn_num_heads=4,
                    attn_num_kv_heads=2, remat=False, kv_page_tokens=8,
                    kv_slot_tokens=64, **kw)


def spec(cfg, k=K):
    return dataclasses.replace(cfg, spec_tokens=k)


def mixed_prompts(n=4, lo=4, hi=40, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 64, size=int(rng.integers(lo, hi + 1)))
            .astype(np.int32) for _ in range(n)]


def greedy_requests(prompts, max_new=12, eos_id=None):
    return [GenerationRequest(prompt_ids=p.copy(), max_new_tokens=max_new,
                              top_k=1, seed=100 + i, eos_id=eos_id)
            for i, p in enumerate(prompts)]


def run_engine(params, cfg, reqs, capacity=3, **kw):
    eng = ServingEngine(params, cfg, capacity=capacity, tokens_per_tick=2,
                        max_top_k=8, **kw)
    return [r.new_tokens.tolist() for r in eng.run(reqs)], eng


class WrongDrafter(spec_decode.Drafter):
    """Proposes token 1 everywhere: seldom the model's argmax in a
    64-vocab, so nearly every tick rejects at the first draft.  A test
    that needs EVERY draft rejected uses ``ContraryDrafter``."""

    def observe(self, stream, tokens):
        pass

    def draft(self, stream, n):
        return [1] * n

    def forget(self, stream):
        pass


class ContraryDrafter(spec_decode.Drafter):
    """Wrong by construction: told each stream's prompt length and the
    tokens a non-speculative greedy engine emits for it (``expect``),
    it proposes at every position a token other than the one the model
    will choose — every draft is rejected, whatever the seeds."""

    def __init__(self, vocab):
        self.vocab = vocab
        self._truth = {}  # stream -> (prompt length, greedy tokens)
        self._seen = {}   # stream -> tokens observed (prompt included)

    def expect(self, stream, prompt_len, tokens):
        self._truth[stream] = (prompt_len, list(tokens))

    def observe(self, stream, tokens):
        self._seen[stream] = self._seen.get(stream, 0) + len(tokens)

    def draft(self, stream, n):
        prompt_len, truth = self._truth[stream]
        pos = self._seen[stream] - prompt_len
        return [(t + 1) % self.vocab for t in truth[pos:pos + n]]

    def forget(self, stream):
        self._seen.pop(stream, None)


# --------------------------------------------------------- token identity


@pytest.mark.parametrize("layer", ["mamba2", "mamba1"])
def test_spec_engine_matches_nonspec(layer):
    """Greedy speculative engine streams == non-speculative greedy
    streams, token for token (speculation is lossless under argmax)."""
    cfg = tiny_cfg(layer)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    prompts = mixed_prompts()
    base, _ = run_engine(params, cfg, greedy_requests(prompts))
    out, eng = run_engine(params, spec(cfg), greedy_requests(prompts))
    assert out == base
    sp = eng.metrics.summary()["speculation"]
    assert sp["spec_tokens"] == K and sp["drafter"] == "ngram"


def test_spec_engine_matches_nonspec_hybrid():
    cfg = hybrid_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    prompts = mixed_prompts()
    base, _ = run_engine(params, cfg, greedy_requests(prompts))
    out, _ = run_engine(params, spec(cfg), greedy_requests(prompts))
    assert out == base


@pytest.mark.parametrize("layer", ["mamba2", "mamba1"])
def test_spec_generate_matches_engine(layer):
    """generate()'s speculative path runs the identical loop — parity
    by construction (same drafts, same verify step, same decision)."""
    cfg = spec(tiny_cfg(layer))
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    prompts = mixed_prompts(n=2)
    eng_out, _ = run_engine(params, cfg, greedy_requests(prompts))
    for p, stream in zip(prompts, eng_out):
        g = generate(params, cfg, jnp.asarray(p)[None], jax.random.PRNGKey(9),
                     max_new_tokens=12, top_k=1)
        assert np.asarray(g)[0, len(p):].tolist() == stream


def test_spec_chunked_long_prompt_parity():
    """Prompts past the chunk width take the chunked-prefill path on
    both sides; speculation rides on top unchanged."""
    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 64, size=n).astype(np.int32)
               for n in (40, 53, 7)]
    base, _ = run_engine(params, cfg, greedy_requests(prompts))
    out, _ = run_engine(params, spec(cfg), greedy_requests(prompts))
    assert out == base
    g = generate(params, spec(cfg), jnp.asarray(prompts[1])[None],
                 jax.random.PRNGKey(1), max_new_tokens=12, top_k=1)
    assert np.asarray(g)[0, len(prompts[1]):].tolist() == base[1]


def test_spec_hybrid_chunked_long_parity():
    cfg = hybrid_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 64, size=n).astype(np.int32)
               for n in (37, 21)]
    base, _ = run_engine(params, cfg, greedy_requests(prompts, max_new=10))
    out, _ = run_engine(params, spec(cfg), greedy_requests(prompts,
                                                           max_new=10))
    assert out == base
    g = generate(params, spec(cfg), jnp.asarray(prompts[0])[None],
                 jax.random.PRNGKey(1), max_new_tokens=10, top_k=1)
    assert np.asarray(g)[0, len(prompts[0]):].tolist() == base[0]


def test_spec_eos_parity():
    """EOS stopping fires on the same token with speculation on; the
    finish reason and the truncated stream agree."""
    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    prompts = mixed_prompts(n=3, seed=5)
    base, _ = run_engine(params, cfg, greedy_requests(prompts, max_new=16))
    eos = base[0][4]  # a token the first stream actually emits
    def reqs():
        return greedy_requests(prompts, max_new=16, eos_id=eos)
    b, _ = run_engine(params, cfg, reqs())
    s, _ = run_engine(params, spec(cfg), reqs())
    assert b == s
    assert any(len(x) < 16 for x in s)  # eos actually fired somewhere


def test_spec_tp_mesh_parity():
    """The (2,2) tensor-parallel serving mesh: the verify step applies
    the same weight constraint as the chunk step, streams unchanged."""
    cfg = tiny_cfg(serving_data_shards=2, serving_model_shards=2)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    prompts = mixed_prompts(n=4)
    base, _ = run_engine(params, tiny_cfg(), greedy_requests(prompts),
                         capacity=2)
    out, _ = run_engine(params, spec(cfg), greedy_requests(prompts),
                        capacity=2)
    assert out == base


def test_spec_prefix_cache_warm_parity():
    """Prefix-cache warm hits (full AND partial) seed the same state a
    cold run computes; speculative streams stay identical warm vs cold
    — and vs the non-speculative engine."""
    cfg = spec(tiny_cfg(prefix_cache_entries=32))
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    preamble = rng.integers(0, 64, size=2 * CHUNK).astype(np.int32)
    prompts = [np.concatenate([preamble,
                               rng.integers(0, 64, size=6).astype(np.int32)])
               for _ in range(3)]
    base, _ = run_engine(params, tiny_cfg(),
                         greedy_requests(prompts, max_new=8))
    eng = ServingEngine(params, cfg, capacity=3, tokens_per_tick=2,
                        max_top_k=8)
    cold = [r.new_tokens.tolist()
            for r in eng.run(greedy_requests(prompts, max_new=8))]
    warm = [r.new_tokens.tolist()
            for r in eng.run(greedy_requests(prompts, max_new=8))]
    assert cold == base
    assert warm == base
    assert eng.metrics.prefix_full_hits + eng.metrics.prefix_partial_hits > 0


def test_spec_migration_parity():
    """Disaggregated tiers: prefill-tier completion migrates into a
    speculative decode replica; the reseeded pending token comes from
    the artifact's logits, so migrated streams match solo generate()
    and the non-speculative fabric."""
    cfg = spec(tiny_cfg(disagg_prompt_threshold=CHUNK))
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 64, size=n).astype(np.int32)
               for n in (40, 6, 25)]

    def run_router(c):
        router = RequestRouter(params, c, num_replicas=2, capacity=3,
                               tokens_per_tick=2, max_top_k=8,
                               roles=["prefill", "decode"])
        return ([r.new_tokens.tolist()
                 for r in router.run(greedy_requests(prompts, max_new=8))],
                router)

    base, _ = run_router(dataclasses.replace(cfg, spec_tokens=0))
    out, router = run_router(cfg)
    assert out == base
    assert router.migrations > 0


# ------------------------------------------------------- rollback invariants


def test_rejection_rollback_restores_carries_bitexact():
    """An always-wrong drafter forces a rollback every tick; the
    conv/SSM carries of every slot must come back bit-identical to the
    pre-tick snapshot (the per-row select keeps the old blocks)."""
    cfg = spec(tiny_cfg())
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    # SHORT prompts: both admit one-shot in the first step, so the
    # second step is a pure all-reject verify tick (no prefill writes
    # between the snapshot and the comparison), and the pending queues
    # (2 < K+1 trusted tokens) cannot trigger a catch-up advance
    prompts = mixed_prompts(n=2, lo=4, hi=8)
    truth, _ = run_engine(params, tiny_cfg(),
                          greedy_requests(prompts, max_new=16), capacity=2)
    drafter = ContraryDrafter(cfg.vocab_size)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2,
                        max_top_k=8, drafter=drafter)
    for r, tokens in zip(greedy_requests(prompts, max_new=16), truth):
        drafter.expect(eng.submit(r), len(r.prompt_ids), tokens)
    eng.step()  # admissions + first verify tick
    before = jax.tree.map(np.asarray, eng.pool["state"]["blocks"])
    events = eng.step()
    assert events  # every tick still commits >= 1 token per stream
    after = jax.tree.map(np.asarray, eng.pool["state"]["blocks"])
    for b, a in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(b, a)
    # acceptance telemetry saw only rejections
    assert eng.metrics.spec_accepted == 0
    assert eng.metrics.spec_drafted > 0


def test_rejection_rollback_preserves_live_kv_pages():
    """Hybrid rollback: a rejected tick's draft KV writes land past
    each row's ``lengths`` (dead by contract) — every LIVE cell of the
    page pool is bit-identical before and after, including pages that
    were RECYCLED from an evicted request (the alias case: a stale
    table could otherwise let draft garbage clobber the new tenant)."""
    cfg = spec(hybrid_cfg())
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2,
                        max_top_k=8, drafter=WrongDrafter())
    # first tenant: run a short request to completion so its pages
    # free and recycle to the next admission
    eng.run(greedy_requests(mixed_prompts(n=1, seed=2), max_new=4))
    for r in greedy_requests(mixed_prompts(n=2, seed=3), max_new=16):
        eng.submit(r)
    while not any(t.status.value == "decode" for t in eng._slots.values()):
        eng.step()
    # some of the new tenants' pages are recycled ids
    held = [p for t in eng._slots.values() if t.pages for p in t.pages]
    assert held, "expected live page allocations"
    kv_len = eng._kv_len.copy()
    tbl = eng._page_tbl.copy()
    before = [np.asarray(x)
              for x in jax.tree.leaves(eng.pool["state"]["attn_blocks"])]
    eng.step()  # one all-reject verify tick
    after = [np.asarray(x)
             for x in jax.tree.leaves(eng.pool["state"]["attn_blocks"])]
    pg = cfg.kv_page_tokens
    for slot in range(eng.capacity):
        # every live cell [0, kv_len) of every held page: bit-equal
        for j in range(tbl.shape[1]):
            phys = int(tbl[slot, j])
            if phys == 0:
                continue
            live = int(min(max(kv_len[slot] - j * pg, 0), pg))
            if not live:
                continue
            for b, a in zip(before, after):
                np.testing.assert_array_equal(
                    b[:, phys, :, :live], a[:, phys, :, :live]
                )


def test_pending_catchup_commits_every_tick():
    """With every draft rejected the pending queue grows to the verify
    width and drains through pure catch-up ticks — the stream still
    advances >= 1 token per tick and stays correct."""
    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    prompts = mixed_prompts(n=2, seed=9)
    base, _ = run_engine(params, cfg, greedy_requests(prompts))
    out, _ = run_engine(params, spec(cfg), greedy_requests(prompts),
                        drafter=WrongDrafter())
    assert out == base


def test_model_drafter_parity_and_error():
    """A companion-model drafter changes the accept pattern, never the
    tokens; spec_drafter='model' without an instance raises the named
    error."""
    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    draft_cfg = dataclasses.replace(cfg, n_layer=1, d_model=16)
    draft_params = init_lm_params(jax.random.PRNGKey(5), draft_cfg)
    prompts = mixed_prompts(n=2, seed=13)
    base, _ = run_engine(params, cfg, greedy_requests(prompts))
    mcfg = dataclasses.replace(spec(cfg), spec_drafter="model")
    out, _ = run_engine(params, mcfg, greedy_requests(prompts),
                        drafter=ModelDrafter(draft_params, draft_cfg))
    assert out == base
    with pytest.raises(ValueError, match="explicit drafter instance"):
        ServingEngine(params, mcfg, capacity=2, max_top_k=8)
    with pytest.raises(ValueError, match="pure-SSM"):
        ModelDrafter(params, hybrid_cfg())


# ------------------------------------------------------------ traces + knobs


def test_spec_trace_counts_flat():
    """Once warm, more requests / different accept patterns add zero
    verify/commit traces — the whole point of the static feed width."""
    cfg = spec(tiny_cfg())
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    run_engine(params, cfg, greedy_requests(mixed_prompts(n=3, seed=1)))
    counts0 = dict(spec_decode.TRACE_COUNTS)
    run_engine(params, cfg, greedy_requests(mixed_prompts(n=4, seed=2)),
               drafter=WrongDrafter())
    run_engine(params, cfg, greedy_requests(mixed_prompts(n=2, seed=3)))
    assert dict(spec_decode.TRACE_COUNTS) == counts0


def test_spec_off_is_byte_stable(tmp_path):
    """K=0: no drafter, no spec stamps on tick records, summary section
    None — the exact pre-spec engine."""
    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    path = tmp_path / "ticks.jsonl"
    metrics = ServingMetrics(2, jsonl_path=str(path))
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2,
                        max_top_k=8, metrics=metrics)
    eng.run(greedy_requests(mixed_prompts(n=2), max_new=4))
    assert eng.drafter is None and not eng.spec
    assert metrics.summary()["speculation"] is None
    for line in open(path):
        rec = json.loads(line)
        assert "spec_drafted" not in rec and "spec_accepted" not in rec


def test_spec_rejects_non_greedy_submit():
    cfg = spec(tiny_cfg())
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, capacity=2, max_top_k=8)
    with pytest.raises(ValueError, match="greedy-only"):
        eng.submit(GenerationRequest(prompt_ids=np.arange(4, dtype=np.int32),
                                     top_k=5))


def test_spec_budget_debit():
    """Verify lanes debit the next step's chunk-prefill budget: with the
    budget sized just past one chunk, a live verify tick's K+1-lane debt
    drops the next step from two chunk grants to the single guaranteed
    one."""
    cfg = spec(tiny_cfg(prefill_tokens_per_tick=CHUNK + 2))
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, capacity=3, tokens_per_tick=2,
                        max_top_k=8)
    rng = np.random.default_rng(21)
    # one short request decodes (a live verify tick every step) while
    # two long prompts want chunk budget
    eng.submit(greedy_requests([rng.integers(0, 64, size=4)
                                .astype(np.int32)], max_new=24)[0])
    eng.step()  # short admits + first verify tick -> debt = 1 * (K+1)
    assert eng._spec_budget_debt == K + 1
    longs = [rng.integers(0, 64, size=3 * CHUNK).astype(np.int32)
             for _ in range(2)]
    for r in greedy_requests(longs, max_new=4):
        eng.submit(r)
    chunks0 = eng.metrics.prefill_chunks
    eng.step()
    # budget 18 - debt 4 = 14 < one chunk: exactly one grant (the
    # progress guarantee), where the undebited budget (18 > 16, loop
    # re-enters while budget remains) would have granted two
    assert eng.metrics.prefill_chunks - chunks0 == 1


# ------------------------------------------------------------------ drafters


def test_ngram_drafter_basics():
    d = NGramDrafter(order=3)
    d.observe("s", [1, 2, 3, 9, 1, 2, 3])
    # trailing [1,2,3] matched earlier -> continuation [9, 1, 2]
    assert d.draft("s", 3) == [9, 1, 2]
    # order fallback: trailing 2-gram only
    d2 = NGramDrafter(order=3)
    d2.observe("s", [5, 6, 7, 6, 7])
    assert d2.draft("s", 2) == [6, 7]
    # no match -> no drafts (fill is the caller's job)
    d3 = NGramDrafter(order=3)
    d3.observe("s", [1, 2, 3, 4, 5])
    assert d3.draft("s", 2) == []
    d.forget("s")
    assert d.draft("s", 2) == []


def test_ngram_drafter_prefers_full_continuation():
    """A periodic tail: the match nearest the end truncates its
    continuation, so the drafter must back off to an earlier full one
    (this is what sustains K-token accepts in argmax cycles)."""
    d = NGramDrafter(order=3)
    d.observe("s", [7] * 12)
    assert d.draft("s", 4) == [7, 7, 7, 7]
    d2 = NGramDrafter(order=2)
    d2.observe("s", [1, 2, 1, 2, 1, 2, 1, 2])
    assert d2.draft("s", 4) == [1, 2, 1, 2]


def test_verify_greedy_decision_rule():
    # full accept: every draft matches the previous position's argmax
    a, adv, nxt = spec_decode.verify_greedy(
        [5, 10, 11], [10, 11, 12], n_trusted=1)
    assert (a, adv, nxt) == (2, True, 12)
    # first rejection: correction = argmax at the last valid position
    a, adv, nxt = spec_decode.verify_greedy(
        [5, 10, 99], [10, 11, 12], n_trusted=1)
    assert (a, adv, nxt) == (1, False, 11)
    # immediate rejection still yields one committed token
    a, adv, nxt = spec_decode.verify_greedy(
        [5, 99, 98], [10, 11, 12], n_trusted=1)
    assert (a, adv, nxt) == (0, False, 10)
    # pure catch-up (all fed trusted): advance + bonus
    a, adv, nxt = spec_decode.verify_greedy(
        [5, 6, 7], [10, 11, 12], n_trusted=3)
    assert (a, adv, nxt) == (0, True, 12)


# ----------------------------------------------------------------- telemetry


def test_spec_telemetry_and_report(tmp_path, capsys):
    """Tick records carry spec_drafted/spec_accepted/spec_streams,
    summary()["speculation"] rolls them up, and obs_report renders the
    "speculation:" line."""
    import subprocess
    import sys
    import os

    cfg = spec(tiny_cfg())
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    path = tmp_path / "spec.jsonl"
    metrics = ServingMetrics(2, jsonl_path=str(path))
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2,
                        max_top_k=8, metrics=metrics)
    eng.run(greedy_requests(mixed_prompts(n=2), max_new=8))
    ticks = [json.loads(l) for l in open(path)
             if json.loads(l).get("kind") == "serving_tick"]
    assert ticks
    for t in ticks:
        assert "spec_drafted" in t and "spec_accepted" in t
        assert t["spec_streams"] >= 0
    sp = metrics.summary()["speculation"]
    assert sp["drafted"] == sum(t["spec_drafted"] for t in ticks)
    assert sp["accepted_tokens_per_tick"] >= 1.0
    assert sp["acceptance_rate_pct_hist"]["count"] == len(
        [t for t in ticks if t["spec_drafted"]])
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "obs_report.py"),
         str(path)],
        capture_output=True, text=True, cwd=repo, timeout=120,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "speculation:" in r.stdout
    assert "accepted tokens/tick" in r.stdout


def test_spec_goodput_counts_rejected_lanes_as_wasted(tmp_path):
    """Goodput honesty: verify lanes are slot_lanes = capacity * (K+1);
    rejected draft lanes land in wasted_token_lanes."""
    cfg = spec(tiny_cfg())
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    path = tmp_path / "g.jsonl"
    metrics = ServingMetrics(2, jsonl_path=str(path))
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2,
                        max_top_k=8, metrics=metrics,
                        drafter=WrongDrafter())
    eng.run(greedy_requests(mixed_prompts(n=2, seed=4), max_new=6))
    ticks = [json.loads(l) for l in open(path)
             if json.loads(l).get("kind") == "serving_tick"]
    for t in ticks:
        lanes = t["useful_tokens"] + t["wasted_token_lanes"]
        assert lanes >= 2 * (K + 1)  # capacity * verify width computed
        assert t["wasted_token_lanes"] > 0  # rejected drafts are waste
