"""Data-parallel serving fabric tests (serving/router.py + replica.py).

The contract under test, per ISSUE 5's acceptance criteria:

  * PARITY — for every request in a mixed multi-replica workload
    (mamba1, mamba2, and a hybrid paged config; short and chunked-long
    prompts), the routed stream is bit-identical to a solo
    ``generate()`` call with the same key, no matter which replica the
    router picked or how placement interleaved.
  * DRAIN — a draining replica takes no new placements but finishes
    everything it holds; no request is lost.
  * FAILOVER — a dead replica's unfinished requests requeue onto the
    survivors and restart from scratch; replay dedup means the consumer
    still sees each token index exactly once, so the merged stream is
    contiguous, duplicate-free, and equal to the failure-free run.
  * SHARDING — with ``serving_data_shards=2`` on the conftest's forced
    8-virtual-device CPU host, slot/page state carries a NamedSharding
    over the mesh's data axis, per-shard host page accounting matches
    the device layout, and trace counts stay flat (one tick compile,
    one chunk compile — sharding annotations must not add signatures).

Runnable standalone: ``pytest -m router``.
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
    ReplicaState,
    RequestRouter,
    ServingEngine,
)

pytestmark = [pytest.mark.router, pytest.mark.serving, pytest.mark.fast]

CHUNK = 16


def tiny_cfg(layer="mamba2", **kw):
    kw.setdefault("prefill_chunk_tokens", CHUNK)
    kw.setdefault("prefill_tokens_per_tick", CHUNK)
    return ModelConfig(d_model=32, n_layer=2, vocab_size=64, ssm_layer=layer,
                       headdim=8, chunk_size=16, d_state=16,
                       compute_dtype="float32", **kw)


def hybrid_cfg(**kw):
    """CPU-runnable hybrid: paged attention KV at layer 1."""
    return tiny_cfg(attn_layer_idx=(1,), attn_num_heads=4,
                    attn_num_kv_heads=2, remat=False, kv_page_tokens=8,
                    kv_slot_tokens=64, **kw)


def rand_prompt(n, seed=1, vocab=64):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, vocab), np.int32
    )


def solo(params, cfg, prompt, key, **kw):
    out = generate(params, cfg, jnp.asarray(prompt, jnp.int32)[None], key, **kw)
    return np.asarray(out)[0, len(prompt):].tolist()


def mixed_requests(n_short=4, n_long=2, max_new=6, vocab=64):
    """Short prompts plus chunk-spanning longs (> 2 * CHUNK tokens)."""
    reqs = []
    for i in range(n_short):
        reqs.append(GenerationRequest(
            prompt_ids=rand_prompt(5 + 3 * i, seed=10 + i, vocab=vocab),
            max_new_tokens=max_new, key=jax.random.PRNGKey(100 + i)))
    for i in range(n_long):
        reqs.append(GenerationRequest(
            prompt_ids=rand_prompt(2 * CHUNK + 7 + i, seed=50 + i,
                                   vocab=vocab),
            max_new_tokens=max_new, key=jax.random.PRNGKey(200 + i)))
    return reqs


def assert_parity(params, cfg, requests, results):
    for r, res in zip(requests, results):
        want = solo(params, cfg, r.prompt_ids, r.key,
                    max_new_tokens=r.max_new_tokens)
        assert res.new_tokens.tolist() == want


# ----------------------------------------------------------------- parity


@pytest.mark.parametrize("layer", ["mamba2", "mamba1"])
def test_mixed_parity_two_replicas(layer):
    """Every routed stream bit-matches solo generate() — short and
    chunked-long prompts over 2 replicas."""
    cfg = tiny_cfg(layer)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    reqs = mixed_requests()
    router = RequestRouter(params, cfg, num_replicas=2, capacity=3,
                           tokens_per_tick=2)
    results = router.run(reqs)
    assert len(results) == len(reqs)
    assert_parity(params, cfg, reqs, results)
    # least-loaded placement actually spread the work
    placed = router.summary()
    assert all(s["finished_requests"] > 0 for s in placed.values())


def test_hybrid_paged_parity_two_replicas():
    """The hybrid paged-KV config routes and keeps parity too."""
    cfg = hybrid_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    reqs = mixed_requests(n_short=3, n_long=1)
    router = RequestRouter(params, cfg, num_replicas=2, capacity=2,
                           tokens_per_tick=2)
    results = router.run(reqs)
    assert_parity(params, cfg, reqs, results)
    # pages fully recycled on both replicas after the drain
    for rep in router.replicas:
        assert rep.engine.page_pool.pages_in_use == 0


def test_streamed_events_are_contiguous():
    """serve() yields each request's token indices 0..n-1 in order,
    with global ids."""
    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    reqs = mixed_requests(n_short=3, n_long=0)
    router = RequestRouter(params, cfg, num_replicas=2, capacity=2,
                           tokens_per_tick=2)
    seen: dict[int, int] = {}
    for ev in router.serve(reqs):
        assert ev.index == seen.get(ev.request_id, 0)
        seen[ev.request_id] = ev.index + 1
    assert sorted(seen) == list(range(len(reqs)))
    assert all(n == r.max_new_tokens for n, r in zip(seen.values(), reqs))


# ------------------------------------------------------------ lifecycle


def test_drain_finishes_resident_work_and_takes_no_new():
    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    reqs = mixed_requests(n_short=4, n_long=0)
    router = RequestRouter(params, cfg, num_replicas=2, capacity=4,
                           tokens_per_tick=2)
    first = [router.submit(r) for r in reqs[:2]]
    router.step()  # both replicas now hold work
    router.drain(0)
    assert router.replicas[0].state is ReplicaState.DRAINING
    held_by_0 = {gid for gid in first
                 if router._routed[gid].replica_id == 0}
    assert held_by_0  # least-loaded placement spread the first two
    late = [router.submit(r) for r in reqs[2:]]
    # new placements all avoided the draining replica
    assert all(router._routed[g].replica_id == 1 for g in late)
    for _ in router.serve():
        pass
    assert router.pending == 0  # nothing lost — drained work finished
    assert len(router.results) == len(reqs)
    assert_parity(params, cfg, reqs,
                  [router.results[i] for i in first + late])


def test_drain_all_replicas_rejects_new_submits():
    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    router = RequestRouter(params, cfg, num_replicas=2, capacity=2)
    router.drain(0)
    router.drain(1)
    with pytest.raises(RuntimeError, match="no accepting replicas"):
        router.submit(mixed_requests(n_short=1, n_long=0)[0])


@pytest.mark.parametrize("layer", ["mamba2", "hybrid"])
def test_failover_no_loss_no_duplicates(layer):
    """Kill a replica mid-decode: its requests requeue, restart, and the
    consumer's merged stream is still exactly the solo generate() run —
    nothing lost, nothing delivered twice."""
    cfg = hybrid_cfg() if layer == "hybrid" else tiny_cfg(layer)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    reqs = mixed_requests(n_short=3, n_long=1, max_new=8)
    router = RequestRouter(params, cfg, num_replicas=2, capacity=4,
                           tokens_per_tick=2)
    ids = [router.submit(r) for r in reqs]
    streams: dict[int, list] = {i: [] for i in ids}
    indices: dict[int, list] = {i: [] for i in ids}

    def take(events):
        for ev in events:
            streams[ev.request_id].append(ev.token)
            indices[ev.request_id].append(ev.index)

    # step until the victim has streamed at least one token, so the
    # failover really does have delivered indices to suppress
    victim = router._routed[ids[0]].replica_id
    victims = [g for g in ids if router._routed[g].replica_id == victim]
    while not any(streams[g] for g in victims):
        take(router.step())
    moved = router.fail(victim)
    # finished requests are pruned from _routed, so membership == live
    assert set(moved) == {g for g in victims if g in router._routed}
    assert router.replicas[victim].state is ReplicaState.DEAD
    assert router.replicas[victim].pending == 0
    for _ in range(10_000):
        if not router.pending:
            break
        take(router.step())
    assert router.pending == 0
    for gid, req in zip(ids, reqs):
        want = solo(params, cfg, req.prompt_ids, req.key,
                    max_new_tokens=req.max_new_tokens)
        assert streams[gid] == want  # no loss, no dups, bit-identical
        assert indices[gid] == list(range(len(want)))  # contiguous


def test_failed_replica_requests_land_on_survivor():
    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    reqs = mixed_requests(n_short=4, n_long=0)
    router = RequestRouter(params, cfg, num_replicas=2, capacity=4,
                           tokens_per_tick=2)
    ids = [router.submit(r) for r in reqs]
    router.step()
    router.fail(0)
    assert all(r.replica_id == 1 for r in router._routed.values())
    for _ in router.serve():
        pass
    assert_parity(params, cfg, reqs, [router.results[i] for i in ids])


def test_failover_with_no_survivors_raises_before_moving():
    """fail() with nothing accepting raises up front — no half-moved
    state — and a later step() refuses to busy-loop on the stranded
    work instead of spinning silently."""
    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    reqs = mixed_requests(n_short=2, n_long=0)
    router = RequestRouter(params, cfg, num_replicas=2, capacity=2,
                           tokens_per_tick=2)
    ids = [router.submit(r) for r in reqs]  # least-loaded: one on each
    assert {router._routed[g].replica_id for g in ids} == {0, 1}
    router.drain(1)
    with pytest.raises(RuntimeError, match="nothing to fail over"):
        router.fail(0)
    # the victim still points at replica 0, untouched by the aborted move
    assert router._routed[ids[0]].replica_id in (0, 1)
    victims = [g for g in ids if router._routed[g].replica_id == 0]
    assert victims and all(
        (0, router._routed[g].local_id) in router._by_local
        for g in victims)
    # the draining replica finishes ITS request; then the stranded one
    # trips the busy-loop guard instead of spinning forever
    with pytest.raises(RuntimeError, match="stranded on dead"):
        for _ in router.serve():
            pass
    assert router.pending == len(victims)


def test_streaming_mode_keeps_no_finished_state():
    """retain_results=False (the long-lived streaming server): finished
    requests leave no router-side state behind — no token buffers, no
    routing-table entries — so memory is bounded by in-flight work."""
    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    reqs = mixed_requests(n_short=3, n_long=0)
    router = RequestRouter(params, cfg, num_replicas=2, capacity=2,
                           tokens_per_tick=2, retain_results=False)
    n_tokens = sum(1 for _ in router.serve(reqs))
    assert n_tokens == sum(r.max_new_tokens for r in reqs)
    assert router._routed == {} and router._by_local == {}
    assert router.results == {}
    with pytest.raises(ValueError, match="retain_results"):
        router.run([])


# ------------------------------------------------------------- sharding


def _shard_mesh_axes(arr):
    """Names the NamedSharding spec actually partitions over."""
    spec = arr.sharding.spec
    return {ax for entry in spec if entry for ax in
            (entry if isinstance(entry, tuple) else (entry,))}


def test_sharded_pool_carries_namedsharding():
    """serving_data_shards=2: slot/page state is NamedSharding-partitioned
    over the serving mesh's data axis, params replicated."""
    from jax.sharding import NamedSharding

    cfg = tiny_cfg(serving_data_shards=2)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, capacity=4)
    # the serving mesh is 2-D (data, model); data-only configs carry a
    # size-1 model axis so the tp knob composes without a mesh rebuild
    assert eng.mesh is not None
    assert dict(eng.mesh.shape) == {"data": 2, "model": 1}
    # logits (S, V) and every meta leaf (S, ...) shard the slot axis
    assert isinstance(eng.pool["logits"].sharding, NamedSharding)
    assert _shard_mesh_axes(eng.pool["logits"]) == {"data"}
    for leaf in jax.tree.leaves(eng.pool["meta"]):
        assert _shard_mesh_axes(leaf) == {"data"}
    # blocks leaves (L, S, ...) shard axis 1 = the slot axis
    for leaf in jax.tree.leaves(eng.pool["state"]):
        assert _shard_mesh_axes(leaf) == {"data"}
    # params replicated (no partitioned axis anywhere)
    for leaf in jax.tree.leaves(eng._params):
        assert _shard_mesh_axes(leaf) == set()


def test_sharded_hybrid_page_accounting_matches_layout():
    """Host page bookkeeping mirrors the device tiles: each slot draws
    only from its own shard's contiguous page range."""
    from mamba_distributed_tpu.serving.state_cache import (
        PagePool,
        page_shard_ranges,
    )

    cfg = hybrid_cfg(serving_data_shards=2)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, capacity=4)
    pool = eng.page_pool
    assert pool.num_shards == 2
    # rounded so (pages + trash) tiles evenly over the data axis
    assert (pool.num_pages + 1) % 2 == 0
    ranges = page_shard_ranges(pool.num_pages, 2)
    assert ranges[0][0] == 1  # trash page 0 never handed out
    assert ranges[0][1] == ranges[1][0]  # contiguous tiles
    # slots 0-1 live in shard 0, slots 2-3 in shard 1
    assert [eng._slot_shard(s) for s in range(4)] == [0, 0, 1, 1]
    got = pool.alloc(2, shard=1)
    assert all(ranges[1][0] <= p < ranges[1][1] for p in got)
    pool.free(got)
    assert pool.free_pages_in(1) == pool.shard_capacity(1)
    # standalone PagePool sanity: shard-range misfit is a loud error
    with pytest.raises(ValueError, match="does not divide"):
        PagePool(10, num_shards=4)
    # ... and so is a pool so small shard 0's tile is just the trash page
    with pytest.raises(ValueError, match="shard 0"):
        PagePool(3, num_shards=4)


def test_sharded_engine_parity_and_flat_traces():
    """The sharded tick decodes bit-identically to solo generate() and
    compiles exactly once per bucket (sharding constraints add no
    signatures): the ISSUE's trace-count pin."""
    from mamba_distributed_tpu.serving.engine import TRACE_COUNTS
    from mamba_distributed_tpu.serving.prefill import (
        TRACE_COUNTS as CHUNK_COUNTS,
    )

    cfg = tiny_cfg(serving_data_shards=2)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, capacity=4, tokens_per_tick=2)
    reqs = mixed_requests(n_short=3, n_long=1)
    t0, c0 = TRACE_COUNTS["tick"], CHUNK_COUNTS["chunk"]
    results = eng.run(reqs)
    assert TRACE_COUNTS["tick"] == t0 + 1  # one tick compile total
    assert CHUNK_COUNTS["chunk"] == c0 + 1  # one chunk compile total
    # a second identical workload retraces NOTHING
    reqs2 = mixed_requests(n_short=3, n_long=1)
    eng.run(reqs2)
    assert TRACE_COUNTS["tick"] == t0 + 1
    assert CHUNK_COUNTS["chunk"] == c0 + 1
    # parity LAST: the solo generate() reference drives the same jitted
    # chunk step with arrays that live on no mesh, and an array's type
    # carries its mesh, so the reference traces a copy of its own —
    # which is not an engine compile and must not count as one
    assert_parity(params, cfg, reqs, results)


def test_sharded_pool_rejects_request_bigger_than_any_shard():
    """A sharded pool confines each slot to its own shard's page range,
    so a request wider than ANY shard can never be admitted even though
    the TOTAL pool covers it — pre-PR the admission check compared
    against the total and would have waited forever."""
    cfg = hybrid_cfg(kv_pool_pages=9, serving_data_shards=2)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, capacity=2, tokens_per_tick=2)
    assert eng._max_shard_pages() == 5  # 10 rows / 2 shards, minus trash
    big = GenerationRequest(prompt_ids=rand_prompt(40, seed=1),
                            max_new_tokens=4,
                            key=jax.random.PRNGKey(0))  # 6 pages
    with pytest.raises(ValueError, match="shard"):
        eng.submit(big)
    # the identical request IS servable on the unsharded pool
    solo_eng = ServingEngine(
        params, hybrid_cfg(kv_pool_pages=9), capacity=2, tokens_per_tick=2)
    rid = solo_eng.submit(big)
    while solo_eng.pending:
        solo_eng.step()
    assert len(solo_eng.results[rid].new_tokens) == 4


def test_sharded_capacity_must_divide():
    cfg = tiny_cfg(serving_data_shards=2)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="divide over"):
        ServingEngine(params, cfg, capacity=3)


def test_router_over_sharded_replicas_parity():
    """The full fabric: 2 replicas, each slot pool sharded 2-way over
    the forced-multi-device host — streams still bit-match generate()."""
    cfg = tiny_cfg(serving_data_shards=2)
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    reqs = mixed_requests(n_short=3, n_long=1)
    router = RequestRouter(params, cfg, num_replicas=2, capacity=2,
                           tokens_per_tick=2)
    results = router.run(reqs)
    assert_parity(params, cfg, reqs, results)
    for rep in router.replicas:
        assert rep.engine.num_shards == 2


# ------------------------------------------------------------ telemetry


def test_route_spans_and_replica_stamped_records(tmp_path):
    """Placement emits one serving_route span per submit (replica, cost,
    queue depth), and the shared jsonl stream's tick/request records
    carry replica ids obs_report can split."""
    from mamba_distributed_tpu.obs import SpanTracer

    cfg = tiny_cfg()
    params = init_lm_params(jax.random.PRNGKey(0), cfg)
    spans_path = str(tmp_path / "spans.jsonl")
    serve_path = str(tmp_path / "serve.jsonl")
    tracer = SpanTracer(spans_path)
    reqs = mixed_requests(n_short=4, n_long=0)
    router = RequestRouter(params, cfg, num_replicas=2, capacity=2,
                           tokens_per_tick=2, jsonl_path=serve_path,
                           tracer=tracer)
    router.run(reqs)
    spans = [json.loads(l) for l in open(spans_path)]
    routes = [s for s in spans
              if s.get("kind") == "span" and s["name"] == "serving_route"]
    assert len(routes) == len(reqs)
    for s in routes:
        assert s["replica"] in (0, 1)
        assert "cost" in s and "queue_depth" in s and "request_id" in s
    recs = [json.loads(l) for l in open(serve_path)]
    assert {r["replica"] for r in recs
            if r["kind"] == "serving_tick"} == {0, 1}
    assert all(r.get("replica") in (0, 1) for r in recs
               if r["kind"] == "request")
    # obs_report renders the per-replica table from the same stream
    import scripts.obs_report as obs_report

    report = obs_report.build_report(recs)
    assert sorted(report["replicas"]) == [0, 1]
    for row in report["replicas"].values():
        assert row["requests"] > 0 and row["ticks"] > 0
    assert "per-replica" in obs_report.format_report(report)
