"""End-to-end CLI smoke: train.py -> checkpoint -> generate.py + eval.py.

Everything runs as real subprocesses on the CPU backend, zero-egress
(toy BPE files, toy HellaSwag jsonl) — the same drive the verify recipe
does by hand (.claude/skills/verify/SKILL.md)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(bpe_dir=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if bpe_dir:
        env["GPT2_BPE_DIR"] = bpe_dir
    return env


def _run(args, env):
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, cwd=REPO, env=env, timeout=900)


@pytest.mark.slow
def test_cli_train_generate_eval_roundtrip(tmp_path):
    from tests.conftest import make_toy_bpe

    # toy BPE (identity byte vocab — enough for encode/decode plumbing)
    bpe = make_toy_bpe(tmp_path / "bpe")
    env = _env(bpe)

    # --- train 4 steps, checkpoint every 2 ---
    p = _run(
        ["train.py", "--preset", "mamba2-tiny", "--max-steps", "4",
         "--data-dir", str(tmp_path / "data"),
         "--log-dir", str(tmp_path / "log"),
         "--checkpoint-dir", str(tmp_path / "ckpt"),
         "--checkpoint-every", "2", "--sample-prompt", "Hello"],
        env,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    log = (tmp_path / "log" / "log.txt").read_text().splitlines()
    assert any(line.split()[1] == "train" for line in log)

    # --- resume continues from the checkpoint, preserving history ---
    p = _run(
        ["train.py", "--preset", "mamba2-tiny", "--max-steps", "6",
         "--data-dir", str(tmp_path / "data"),
         "--log-dir", str(tmp_path / "log"),
         "--checkpoint-dir", str(tmp_path / "ckpt"), "--resume"],
        env,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    assert "resumed from step" in p.stdout

    # --- generate from the checkpoint (vendored-BPE prompt) ---
    p = _run(
        ["generate.py", "--checkpoint", str(tmp_path / "ckpt"),
         "--preset", "mamba2-tiny", "--prompt", "Hello",
         "--max-new-tokens", "4", "--num-return", "1"],
        env,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.strip().startswith(">")

    # --- HellaSwag CLI on the committed synthetic jsonl, emitting a real
    # acc_norm line (VERDICT r4 item 7) ---
    import re

    hs = os.path.join(REPO, "tests", "data", "hellaswag_tiny.jsonl")
    p = _run(
        ["eval.py", "-m", "custom", "--checkpoint", str(tmp_path / "ckpt"),
         "--preset", "mamba2-tiny", "--data-file", hs,
         "--bpe-dir", str(bpe), "--limit", "16",
         "--log-file", str(tmp_path / "hs_out.txt")],
        env,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    assert "acc_norm" in p.stdout  # result dict printed by eval.py
    line = (tmp_path / "hs_out.txt").read_text()
    # exact reference writer format (ref eval.py:180-183 appends
    # f"{total} {correct_norm}/{total} {acc_norm:.4f}", sample artifact
    # "2000 648/2000 0.3240")
    assert re.fullmatch(r"16 \d{1,2}/16 [01]\.\d{4}", line), repr(line)


def _write_service_cfg(tmp_path):
    """Tiny CPU config JSON shared by the service CLI smokes."""
    from mamba_distributed_tpu.config import ModelConfig
    from mamba_distributed_tpu.serving.service.worker import config_to_json

    cfg = ModelConfig(d_model=32, n_layer=2, vocab_size=64,
                      ssm_layer="mamba2", headdim=8, chunk_size=16,
                      d_state=16, compute_dtype="float32",
                      prefill_chunk_tokens=16, prefill_tokens_per_tick=16)
    path = str(tmp_path / "service_cfg.json")
    config_to_json(cfg, path)
    return path


@pytest.mark.service
@pytest.mark.serving
def test_serve_worker_cli_smoke(tmp_path):
    """serve_worker.py spawns, prints its READY line, answers
    hello/ping over the wire, and SIGTERM-drains to a clean exit
    (ISSUE 13 satellite: service CLI smoke).  No generation — the
    streamed-request path is covered by test_service.py — so the smoke
    stays compile-free and cheap in the tier-1 window."""
    import signal
    import socket

    from mamba_distributed_tpu.serving.service import wire

    cfg_path = _write_service_cfg(tmp_path)
    env = _env()
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "scripts", "serve_worker.py"),
         "--config", cfg_path, "--replica-id", "0", "--capacity", "2",
         "--port", "0"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=REPO, env=env,
    )
    try:
        port = None
        for line in proc.stdout:
            if line.startswith("SERVE_WORKER_READY"):
                fields = dict(kv.split("=") for kv in line.split()[1:])
                port = int(fields["port"])
                assert fields["role"] == "mixed"
                break
        assert port is not None, "worker never printed READY"
        sock = socket.create_connection(("127.0.0.1", port), timeout=10)
        sock.settimeout(10)
        wire.send_msg(sock, "hello", {})
        mtype, payload = wire.recv_msg(sock)
        assert mtype == "hello" and payload["replica_id"] == 0
        assert payload["stats"]["state"] == "active"
        wire.send_msg(sock, "ping", {})
        assert wire.recv_msg(sock)[0] == "pong"
        sock.close()
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=60) == 0
    finally:
        proc.kill()


@pytest.mark.service
@pytest.mark.serving
@pytest.mark.slow
def test_serve_fabric_cli_smoke(tmp_path):
    """serve_fabric.py --spawn 1 end to end: READY line, /healthz with
    a beating worker, one streamed SSE request, /drain with requeue,
    and a clean SIGTERM rolling shutdown (worker included).  Marked
    slow: it compiles a worker engine inside the smoke — the same
    surface runs un-marked in tests/test_service.py through the
    library entrypoints."""
    import json
    import signal

    from mamba_distributed_tpu.serving.service import client as svc_client

    cfg_path = _write_service_cfg(tmp_path)
    env = _env()
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "scripts", "serve_fabric.py"),
         "--config", cfg_path, "--spawn", "1", "--http-port", "0",
         "--capacity", "2", "--tokens-per-tick", "2",
         "--jsonl", str(tmp_path / "health.jsonl")],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=REPO, env=env,
    )
    try:
        port = None
        for line in proc.stdout:
            if line.startswith("SERVE_FABRIC_READY"):
                fields = dict(kv.split("=") for kv in line.split()[1:])
                port = int(fields["port"])
                assert fields["workers"] == "1"
                break
        assert port is not None, "fabric never printed READY"
        hz = svc_client.http_json("127.0.0.1", port, "GET", "/healthz")
        assert hz["ok"] and hz["replicas"]["0"]["state"] == "active"
        res = svc_client.stream_generate(
            "127.0.0.1", port,
            {"prompt_ids": [1, 2, 3, 4], "max_new_tokens": 3, "seed": 7},
            timeout=300,
        )
        assert len(res["tokens"]) == 3
        assert res["finish_reason"] == "length"
        assert res["ttft_ms"] is not None
        out = svc_client.http_json("127.0.0.1", port, "POST", "/drain/0")
        assert out["_status"] == 200 and out["replica"] == 0
        hz = svc_client.http_json("127.0.0.1", port, "GET", "/healthz")
        assert hz["replicas"]["0"]["state"] == "draining"
        # heartbeat records landed on the obs stream
        recs = [json.loads(ln)
                for ln in open(tmp_path / "health.jsonl") if ln.strip()]
        assert any(r["event"] == "beat" for r in recs)
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=120) == 0
    finally:
        proc.kill()


@pytest.mark.obs
@pytest.mark.metrics
@pytest.mark.fast
def test_metrics_schema_gate(tmp_path):
    """The /metrics schema drift gate (ISSUE 17 satellite): every
    family obs/prom.py can emit is documented in the OBSERVABILITY.md
    metric table and vice versa — and the gate actually fails loud in
    BOTH drift directions."""
    gate = os.path.join(REPO, "scripts", "check_metrics_schema.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, gate], capture_output=True,
                       text=True, cwd=REPO, env=env, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "metrics schema ok" in r.stdout

    # rename one documented family: now one STALE doc row AND one
    # UNDOCUMENTED emitted family
    with open(os.path.join(REPO, "docs", "OBSERVABILITY.md")) as f:
        doc = f.read()
    assert "`mamba_ticks_total`" in doc
    broken = tmp_path / "broken.md"
    broken.write_text(doc.replace("`mamba_ticks_total`",
                                  "`mamba_ticks_renamed`"))
    r = subprocess.run([sys.executable, gate, "--doc", str(broken)],
                       capture_output=True, text=True, cwd=REPO, env=env,
                       timeout=120)
    assert r.returncode == 1
    assert "mamba_ticks_total" in r.stdout  # UNDOCUMENTED
    assert "mamba_ticks_renamed" in r.stdout  # STALE
