"""The configuration ``granite-4.0-h-small`` in the yardstick: its files
against the catalog's row, its reference found by the lookup and held to the
contract, the harness end to end on its tiny twin (the planted faults reading
``correct`` false), the expert kernel's count by hand, and the new metrics'
readers on a recorded span list and hand-made rows."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, reference
from benchmark.kernels import moe_experts
from benchmark.peaks import peaks_of
from benchmark.readers import event_attr, path_scope_roofline, path_share
from benchmark.reference import granite_moe_hybrid as ref
from conftest import CHECKOUT, DATA

MINE = os.path.join(DATA, "granite_moe_hybrid")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "serve-granite-4.0-h-small-chat"
MANIFEST = os.path.join(CHECKOUT, "BENCHMARK.json")


def _config():
    return json.load(open(os.path.join(
        CHECKOUT, "benchmark", "configs", "granite-4.0-h-small.json")))


def _tiny():
    return json.load(open(os.path.join(MINE, "configs", "tiny-granite-h.json")))


# ------------------------------------------------------------ the files


def test_published_keys_as_run():
    """Every key of the published config stands in the file under its own
    name with its own value, but the depth and the experts held; no width,
    the router's width and the experts a token among them."""
    c = _config()
    if not os.path.isfile(CATALOG):
        pytest.skip("the guide's catalog is not on this machine")
    row = next(json.loads(l) for l in open(CATALOG)
               if json.loads(l)["name"] == "granite-4.0-h-small")
    assert c["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if c.get(k) != v}
    assert differs == {"num_hidden_layers", "num_local_experts"}
    assert set(c["reduced"]) == differs | {"n_layer", "moe_experts_held"}
    assert c["published"]["num_hidden_layers"] == row["config"]["num_hidden_layers"]
    assert c["published"]["num_local_experts"] == row["config"]["num_local_experts"]
    m = c["model"]
    assert c["num_hidden_layers"] == m["n_layer"] == 10  # one whole period
    assert c["layer_types"][:10].count("attention") == 1
    assert m["attn_layer_idx"] == [c["layer_types"][:10].index("attention")]
    assert c["num_local_experts"] == m["moe_experts_held"] >= 8  # the floor
    assert m["moe_num_experts"] == row["config"]["num_local_experts"]
    assert m["moe_top_k"] == c["num_experts_per_tok"]
    assert "two chips" in c["deployment"].lower()


def test_model_group_is_the_published_config_under_the_programs_names():
    c = _config()
    m = c["model"]
    same = {"d_model": "hidden_size", "vocab_size": "vocab_size",
            "headdim": "mamba_d_head", "d_state": "mamba_d_state",
            "ngroups": "mamba_n_groups", "d_conv": "mamba_d_conv",
            "chunk_size": "mamba_chunk_size", "expand": "mamba_expand",
            "d_intermediate": "intermediate_size",
            "moe_shared_intermediate": "shared_intermediate_size",
            "attn_num_heads": "num_attention_heads",
            "attn_num_kv_heads": "num_key_value_heads",
            "norm_eps": "rms_norm_eps", "tie_embeddings": "tie_word_embeddings",
            "conv_bias": "mamba_conv_bias", "proj_bias": "mamba_proj_bias",
            "embedding_multiplier": "embedding_multiplier",
            "residual_multiplier": "residual_multiplier",
            "attention_multiplier": "attention_multiplier"}
    for ours, theirs in same.items():
        assert m[ours] == c[theirs], ours
    assert m["lm_head_multiplier"] == 1 / c["logits_scaling"]
    assert m["expand"] * m["d_model"] == c["mamba_n_heads"] * c["mamba_d_head"]
    assert m["attn_head_dim"] * m["attn_num_heads"] == c["hidden_size"]
    assert m["attn_rotary_dim"] == 0 and c["position_embedding_type"] == "nope"


def test_program_runs_what_the_file_states():
    import dataclasses

    from mamba_distributed_tpu.config import get_preset

    c = _config()
    cfg = dataclasses.replace(get_preset(c["preset"]).model, **c["serving"])
    harness.check_config(c["model"], cfg, "granite-4.0-h-small")
    harness.check_config(c["serving"], cfg, "granite-4.0-h-small")
    assert cfg.effective_prefill_chunk_tokens % c["mamba_chunk_size"] == 0
    assert 2 * cfg.num_params() == 9_925_465_344  # 9.93 GB of bfloat16


def test_cell_is_the_chat_cell_but_for_model_slots_and_rate():
    mine = harness.load_cell(CELL, MANIFEST, harness.BENCH_DIR)
    other = harness.load_cell("serve-mamba2-280m-chat", MANIFEST,
                              harness.BENCH_DIR)
    assert mine.chips == 1
    for key in ("kind", "traffic", "trace_seconds", "traffic_source"):
        assert mine.workload[key] == other.workload[key], key
    a, b = dict(mine.workload["mix"]), dict(other.workload["mix"])
    ra, rb = a.pop("arrivals"), b.pop("arrivals")
    assert a == b and {k: v for k, v in ra.items() if k != "rate_per_s"} == \
        {k: v for k, v in rb.items() if k != "rate_per_s"}
    w = mine.workload
    assert w["engine"] == {"capacity": 64}
    assert ra["rate_per_s"] == pytest.approx(0.8 * w["sustained_rate_per_s"])
    assert set(w["limits"]) == {"logit_gap", "incomplete", "window_compiles"}
    # not on ttft_p50_ms (the file's ``ttft_why_not``: its median spread over
    # half the bound on the chip), so on no per-layer metric that moves it
    assert {e["name"] for e in mine.end_to_end} == {"itl_p95_ms", "setup_s"}
    assert "ttft_why_not" in w
    assert {e["moves"] for e in mine.per_layer} == {"itl_p95_ms"}
    names = {e["name"] for e in mine.per_layer}
    assert {"moe_share.chat", "router_share.chat", "expert_roofline.chat",
            "expert_rows_here_share.chat", "expert_load_max_over_mean.chat",
            "tick_ms.chat", "tick_width_p50.chat", "step_mfu.chat"} <= names
    # the pure Mamba-2 pool's byte count does not know this tick's weights
    assert not {"tick_hbm_roofline.chat", "pool_rewrite_share.chat"} & names
    # every page-count bucket a tick can reach is warmed: 1 .. 32 pages
    pages = sorted(-(-(r["prompt_len"] + r["max_new"]) // 64)
                   for phase in w["warmup"] for r in phase)
    assert [1 << (p - 1).bit_length() for p in pages] == [1, 2, 4, 8, 16, 32]


# ------------------------------------------------------------ the reference


def test_lookup_finds_the_reference_with_every_duty():
    assert reference.of(_config()) is ref
    assert ref.STACKED == ("blocks", "attn_blocks")


def test_walk_equals_the_whole_tree_and_the_control_differs():
    """``served_logits`` (a layer's weights drawn as the walk reaches it, the
    embedding read a block at a time) gives the logits of ``logits_fn`` on
    the whole tree from the same key; in fp8 and under each planted fault it
    is the same code and another answer."""
    c = _tiny()
    m, dtype = c["model"], reference.params_dtype(c)
    key = reference.seed_key(2**31 + 5)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 96), 0, m["vocab_size"])
    pos = jnp.arange(64, 96)
    tree = jax.tree.map(lambda a: a.astype(jnp.float32),
                        jax.jit(lambda k: ref.init_params(k, m, dtype))(key))
    want = np.asarray(ref.logits_fn(tree, m, ids))[0, 64:96]
    got = np.asarray(ref.served_logits(key, m, dtype, ids, pos))
    # the same operations on the same rounded values, jitted apart
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-8)
    for other in ("fp8",) + ref.FAULTS:
        low = np.asarray(ref.served_logits(key, m, dtype, ids, pos,
                                           precision=other))
        assert np.abs(low - want).max() > 1e-4, other  # logits of size 0.004
    tgt = jnp.roll(ids, -1, axis=1)
    lg = ref.logits_fn(tree, m, ids)
    ce = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(lg, tgt[..., None], -1)[..., 0]
    assert float(ref.loss_sum(tree, m, ids, tgt)) == pytest.approx(
        float(ce.sum()), rel=1e-6)


def test_weights_are_the_seeds_draw_rounded_once():
    c = _tiny()
    m = c["model"]
    key = reference.seed_key(11)
    full = jax.jit(lambda k: ref.init_params(k, m, "float32"))(key)
    half = jax.jit(lambda k: ref.init_params(k, m, "bfloat16"))(key)
    for a, b in zip(jax.tree.leaves(full), jax.tree.leaves(half)):
        assert b.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.bfloat16), np.float32),
                                      np.asarray(b, np.float32))
    # the program's layout: two stacks, the held experts stacked, the router
    # whole, the shared expert beside them
    assert set(half) == {"embedding", "norm_f", "blocks", "attn_blocks"}
    for stack, n in (("blocks", 2), ("attn_blocks", 2)):
        assert set(half[stack]) == {"norm", "mixer", "norm2", "moe", "shared"}
        assert half[stack]["moe"]["w1"].shape == (n, 4, 64, 48)
        assert half[stack]["moe"]["w2"].shape == (n, 4, 24, 64)
        assert half[stack]["moe"]["router"]["kernel"].shape == (n, 64, 8)
        assert half[stack]["shared"]["fc1"]["kernel"].shape == (n, 64, 96)


def _run_tiny(control=None):
    """The tiny cell through the kind, as ``run.py`` and ``control.py`` run
    it (``control``: the precision or planted fault put in the program's
    place for the second reading)."""
    cwd = os.getcwd()
    try:
        cell, devices, kind = harness.open_cell(
            "tiny-serve-granite-h", os.path.join(MINE, "BENCHMARK.json"), MINE,
            require_tpu=False)
        import time
        return cell, kind.run(cell=cell, seed=2**31 + 3, seconds=2.0,
                              trace=False, devices=devices,
                              t_process=time.perf_counter(), control=control)
    finally:
        os.chdir(cwd)


def test_tiny_cell_runs_end_to_end_and_the_faults_read_false():
    """The expert layer through ``serve_open`` on the CPU: the engine is
    built from the harness's bfloat16 tree, serves the open loop, and what it
    served is the reference's best within the tiny cell's limit; the token a
    planted fault puts first is not, by the same judge and limit."""
    cell, run = _run_tiny(control="fault_no_shared")
    assert run["correct"] is True and run["failed"] == 0
    assert run["attempted"] > 0
    assert run["compared"]["window_compiles"]["value"] == 0
    limit = cell.workload["limits"]["logit_gap"]
    assert run["compared"]["logit_gap"]["value"] <= limit / 4
    ok, compared = harness.judge({"logit_gap": run["control_gap"]},
                                 {"logit_gap": limit})
    assert not ok and compared["logit_gap"]["value"] > 2 * limit
    # the launches' counters reached the spans the metrics read
    for metric, lo, hi in (("expert_rows_here_share.chat", 0.35, 0.65),
                           ("expert_load_max_over_mean.chat", 1.0, 4.0)):
        spec = json.load(open(os.path.join(
            CHECKOUT, "benchmark", "metrics", metric + ".json")))
        assert spec["reader"] == "event_attr"
        assert lo <= event_attr.read(run, **spec["args"]) <= hi, metric


# ------------------------------------------------------------ the counts


def test_operations_by_hand():
    m = _config()["model"]
    experts = 2 * 4096 * 72 + 10 * (36 / 72) * 6 * 4096 * 768 + 6 * 4096 * 1536
    mamba = 2 * (4096 * 16768 + 8192 * 4096 + 8448 * 4 + 2 * 128 * 128 * 64)
    attn = 2 * (4096 * 6144 + 4096 * 4096)
    assert ref.expert_layer_flops(m) == experts
    assert ref.layer_flops(m, 0, False) == mamba + experts
    assert ref.layer_flops(m, 0, True) == attn + experts
    base = ref.forward_flops_per_token(m, 0)
    assert base == 9 * mamba + attn + 10 * experts
    assert ref.forward_flops_per_token(m, 1000) - base == 4 * 1000 * 32 * 128
    head = 2 * 4096 * 100352
    assert ref.forward_flops_per_token(m, 0, logit_positions=1.0) == base + head
    assert ref.train_flops_per_token(m, 1024) == 3 * (
        ref.forward_flops_per_token(m, 512) + head)


class _Spans:
    def __init__(self, spans):
        self.spans = spans

    def within(self, t0, t1, name=None):
        return [s for s in self.spans
                if t0 <= s[1] < t1 and (name is None or s[0] == name)]


class _Window:
    t_start, t_stop = 0.0, 10.0


def _run():
    spans = _Spans([
        ("serving_prefill_chunk", 1.0, 1.1, {"expert_rows": 2500,
                                             "expert_hits": 360}),
        ("serving_tick", 2.0, 2.2, {"live": 16, "width": 16,
                                    "expert_rows": 6400, "expert_hits": 2800,
                                    "expert_rows_share": 0.5,
                                    "expert_load_max_over_mean": 1.3}),
        ("serving_tick", 3.0, 3.2, {"live": 64, "width": 64,
                                    "expert_rows": 2_000_000,
                                    "expert_hits": 2880,
                                    "expert_rows_share": 0.52,
                                    "expert_load_max_over_mean": 1.1}),
        ("serving_tick", 4.0, 4.2, {"live": 2, "width": 8}),  # no counters
        ("serving_tick", 20.0, 20.2, {"expert_rows": 1, "expert_hits": 1}),
    ])
    return {"model": _config()["model"], "spans": spans, "window": (0.0, 10.0),
            "trace_window": _Window(), "device_kind": "TPU v5 lite",
            "platform": "tpu"}


def test_expert_kernel_counts_by_hand():
    m = _config()["model"]
    ops, by = moe_experts.tick_call(m, 6400, 2800)
    assert ops == 6 * 4096 * 768 * 6400  # up, gate, down: 2 x 3 x d x di a row
    one_expert = 3 * 4096 * 768 * 2  # 18.9 MB of bfloat16
    assert one_expert == 18_874_368
    assert by == 2800 * one_expert + 6400 * 4096 * 6
    peaks = peaks_of("TPU v5 lite")
    assert by / peaks["hbm_bytes_per_s"] > ops / peaks["flops_bf16"]  # by bytes
    run = _run()
    got = moe_experts.calls(run)  # the window's ticks that carry counters
    assert got == [moe_experts.tick_call(m, 6400, 2800),
                   moe_experts.tick_call(m, 2_000_000, 2880)]
    # the second is bound by operations, the first by bytes
    want = (got[0][1] / peaks["hbm_bytes_per_s"]
            + got[1][0] / peaks["flops_bf16"])
    assert moe_experts.least_seconds(run, peaks) == pytest.approx(want)
    assert moe_experts.least_seconds(
        dict(run, spans=_Spans([])), peaks) is None


# ------------------------------------------------------------ the readers


def test_readers_on_a_recorded_span_list_and_hand_made_rows():
    tick = "jit(_tick)/attn_layers/while/body/closed_call/layers/while/body/closed_call"
    chunk = "jit(prefill_chunk)/attn_layers/while/body/closed_call"
    rows = [
        ["a", tick + "/moe/router/dot_general:", 0.02],
        ["b", tick + "/moe/router/top_k:", 0.03],
        ["c", tick + "/moe/experts/nd,edf->enf/dot_general:", 0.30],
        ["d", tick + "/moe/experts/enf,efd->nd/dot_general:", 0.20],
        ["e", tick + "/moe/mlp/dot_general:", 0.05],
        ["f", chunk + "/moe/experts/mul:", 0.04],
        ["g", chunk + "/ragged-dot-none:", 0.06],  # the compiler's own name
        ["h", tick + "/mixer_in_proj/dot_general:", 0.20],
        ["i", None, 0.10],
    ]
    run = dict(_run(), trace={"busy_s": 1.0}, _scope_table=rows,
               _scope_line=True)
    assert path_share.read(run, "^moe$") == pytest.approx(64.0)
    assert path_share.read(run, "^router$") == pytest.approx(5.0)
    # the tick programs' seconds under ``experts`` alone, by path
    assert path_scope_roofline.seconds(rows, "^experts$", "^jit__tick") == \
        pytest.approx(0.50)
    least = moe_experts.least_seconds(run, peaks_of("TPU v5 lite"))
    spec = json.load(open(os.path.join(
        CHECKOUT, "benchmark", "metrics", "expert_roofline.chat.json")))
    assert spec["reader"] == "path_scope_roofline"
    assert path_scope_roofline.read(run, **spec["args"]) == pytest.approx(
        100 * least / 0.50)
    args = dict(kernel="moe_experts", scope="^experts$")
    assert path_scope_roofline.read(run, program="^jit_no_such", **args) is None
    # a program with no such scope or counter, as the parent: nothing, no raise
    bare = dict(run, _scope_table=[r for r in rows if r[1] and "moe" not in r[1]])
    assert path_scope_roofline.read(bare, **spec["args"]) is None
    assert path_share.read(bare, "^moe$") is None
    assert path_scope_roofline.read({"platform": "cpu"}, **spec["args"]) is None
    none = dict(run, spans=_Spans([("serving_tick", 2.0, 2.2, {"live": 1})]))
    assert path_scope_roofline.read(none, **spec["args"]) is None
    # the two counters' medians over the window's ticks that carry them
    for metric, want in (("expert_rows_here_share.chat", 0.51),
                         ("expert_load_max_over_mean.chat", 1.2)):
        spec = json.load(open(os.path.join(
            CHECKOUT, "benchmark", "metrics", metric + ".json")))
        assert event_attr.read(run, **spec["args"]) == pytest.approx(want)
        assert event_attr.read(none, **spec["args"]) is None


def test_manifest_appends_and_changes_nothing_else():
    """The six cells, one of them on four chips; the new cell at the end of
    every list it joins."""
    b = json.load(open(MANIFEST))
    assert [w["name"] for w in b["workloads"]][-1] == CELL
    assert len(b["workloads"]) == 6
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1
    assert b["configs"][-1]["name"] == "granite-4.0-h-small"
    for e in b["end_to_end"] + b["per_layer"]:
        if CELL in e.get("workloads", []):
            assert e["workloads"][-1] == CELL, e["name"]
    new = [e["name"] for e in b["per_layer"] if e.get("workloads") == [CELL]]
    assert new == ["moe_share.chat", "router_share.chat", "expert_roofline.chat",
                   "expert_rows_here_share.chat", "expert_load_max_over_mean.chat"]
    assert [e["name"] for e in b["per_layer"]][-5:] == new
