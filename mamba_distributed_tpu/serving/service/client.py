"""Tiny stdlib HTTP/SSE client for the fabric front end.

Used by the tests and any operator tooling that wants to drive the
service without pulling in an HTTP library: ``http.client`` with
``Connection: close`` streaming — the SSE body is read line-by-line off
the socket, so TTFT/ITL stamps taken here measure the full wire path
(HTTP parse + SSE framing + the worker RPC hop).
"""

from __future__ import annotations

import http.client
import json
import time


def http_json(host: str, port: int, method: str, path: str,
              body: dict | None = None, timeout: float = 60.0) -> dict:
    """One non-streaming JSON request/response."""
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        payload = None if body is None else json.dumps(body)
        conn.request(method, path, body=payload,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        data = resp.read()
        out = json.loads(data.decode("utf-8")) if data else {}
        out["_status"] = resp.status
        return out
    finally:
        conn.close()


def stream_generate(host: str, port: int, spec: dict,
                    timeout: float = 300.0, on_event=None,
                    path: str = "/v1/generate") -> dict:
    """POST /v1/generate and consume the SSE stream to completion.

    Returns {"tokens": [...], "finish_reason": ..., "events": [...],
    "ttft_ms": ..., "itl_ms": [...]} — client-side latency stamps per
    token.  ``on_event`` (if given) sees each event as it arrives —
    the failover tests use it to know when a stream is mid-flight.
    Raises RuntimeError on an in-stream {"error": ...} event or a
    non-200 status.  Each event carries a ``resume`` cursor while the
    stream is live — feed the last one to ``stream_resume`` to
    re-attach through a restarted front end."""
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        t0 = time.perf_counter()
        conn.request("POST", path, body=json.dumps(spec),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            raise RuntimeError(
                f"{path} -> {resp.status}: "
                f"{resp.read().decode('utf-8', 'replace')[:500]}"
            )
        tokens, events, stamps = [], [], []
        finish_reason, done = None, False
        while True:
            line = resp.fp.readline()
            if not line:
                break
            line = line.decode("utf-8").strip()
            if not line.startswith("data:"):
                continue
            ev = json.loads(line[len("data:"):].strip())
            if "error" in ev:
                raise RuntimeError(f"stream error: {ev['error']}")
            if on_event is not None:
                on_event(ev)
            events.append(ev)
            if "token" in ev:
                # a resumed stream whose cursor already covered every
                # token closes with a bare done marker — no token field
                tokens.append(ev["token"])
                stamps.append(time.perf_counter())
            if ev.get("done"):
                # done is terminal even with finish_reason None — the
                # /v1/resume fully-delivered-cursor close is a bare
                # done marker carrying no reason (server "resumed_empty")
                finish_reason, done = ev.get("finish_reason"), True
                break
        if not done:
            raise RuntimeError(
                f"SSE stream ended without a done event after "
                f"{len(tokens)} token(s)"
            )
        return {
            "tokens": tokens,
            "finish_reason": finish_reason,
            "events": events,
            "ttft_ms": (stamps[0] - t0) * 1000.0 if stamps else None,
            "itl_ms": [(b - a) * 1000.0
                       for a, b in zip(stamps, stamps[1:])],
        }
    finally:
        conn.close()


def stream_resume(host: str, port: int, resume_token: str,
                  timeout: float = 300.0, on_event=None) -> dict:
    """Re-attach an SSE stream from a resume cursor (the ``resume``
    field of the last event a previous connection delivered) through a
    possibly-RESTARTED front end: POST /v1/resume replays everything
    the workers generated past the cursor and keeps streaming to
    completion.  Same return shape as ``stream_generate``."""
    return stream_generate(host, port, {"resume": resume_token},
                           timeout=timeout, on_event=on_event,
                           path="/v1/resume")
