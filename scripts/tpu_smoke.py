"""Real-chip smoke: Pallas kernels vs XLA paths on the local TPU.

The CPU test suite runs the same kernel code in interpret mode; this
script confirms the actual Mosaic lowering agrees on hardware (bf16
matmul precision differs from fp32 CPU — tolerances per the verify-skill
gotcha).  Prints one JSON line per check and exits non-zero on any
mismatch.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mamba_distributed_tpu.utils.platform import init_backend  # noqa: E402

_T0 = time.time()


def _progress(msg: str) -> None:
    print(f"[tpu_smoke +{time.time() - _T0:6.1f}s] {msg}", file=sys.stderr,
          flush=True)


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = init_backend()
    _progress(f"backend up: {len(jax.devices())}x {dev.device_kind}")

    from mamba_distributed_tpu.ops.pallas import (
        selective_scan_pallas,
        ssd_chunked_pallas,
    )
    from mamba_distributed_tpu.ops.scan import selective_scan
    from mamba_distributed_tpu.ops.ssd import ssd_chunked

    ok = True

    def report(name: str, got, ref, atol: float) -> None:
        nonlocal ok
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref.astype(jnp.float32))))
        passed = bool(err <= atol)
        ok = ok and passed
        print(json.dumps({"check": name, "max_abs_err": round(err, 6),
                          "atol": atol, "ok": passed,
                          "device": dev.device_kind}), flush=True)

    with jax.default_matmul_precision("highest"):
        # --- SSD (Mamba-2), 280M-like shapes ---
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        b, t, h, p, n, g = 2, 1024, 24, 64, 128, 1
        x = jax.random.normal(ks[0], (b, t, h, p), jnp.float32)
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)))
        A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
        B = jax.random.normal(ks[3], (b, t, g, n))
        C = jax.random.normal(ks[4], (b, t, g, n))
        D = jnp.ones((h,))
        ref = jax.jit(
            lambda *a: ssd_chunked(*a, chunk_size=256, D=D, compute_dtype=jnp.float32)
        )(x, dt, A, B, C)
        got = jax.jit(
            lambda *a: ssd_chunked_pallas(*a, chunk_size=256, D=D,
                                          compute_dtype=jnp.float32)
        )(x, dt, A, B, C)
        jax.block_until_ready(got)
        _progress("ssd pallas compiled+ran on hardware")
        report("ssd_pallas_fwd_vs_xla_fp32", got, ref, atol=5e-3)

        # --- selective scan (Mamba-1), 280M-like shapes ---
        ks = jax.random.split(jax.random.PRNGKey(1), 5)
        b, t, d, n = 2, 1024, 1536, 16
        u = jax.random.normal(ks[0], (b, t, d))
        delta = jax.random.normal(ks[1], (b, t, d)) * 0.5
        A1 = -jnp.exp(jax.random.normal(ks[2], (d, n)) * 0.3)
        B1 = jax.random.normal(ks[3], (b, t, n))
        C1 = jax.random.normal(ks[4], (b, t, n))
        ref = jax.jit(
            lambda *a: selective_scan(*a, delta_softplus=True)
        )(u, delta, A1, B1, C1)
        got = jax.jit(
            lambda *a: selective_scan_pallas(*a, delta_softplus=True)
        )(u, delta, A1, B1, C1)
        jax.block_until_ready(got)
        _progress("m1 scan pallas compiled+ran on hardware")
        report("m1_scan_pallas_fwd_vs_xla_fp32", got, ref, atol=5e-3)

        # --- odd d: lane-pad fallback must lower on real Mosaic ---
        do = 96
        ref = jax.jit(lambda *a: selective_scan(*a, delta_softplus=True))(
            u[..., :do], delta[..., :do], A1[:do], B1, C1
        )
        got = jax.jit(lambda *a: selective_scan_pallas(*a, delta_softplus=True))(
            u[..., :do], delta[..., :do], A1[:do], B1, C1
        )
        jax.block_until_ready(got)
        _progress("m1 odd-d (96) pallas compiled+ran on hardware")
        report("m1_scan_pallas_odd_d_fwd", got, ref, atol=5e-3)

        # --- backward kernels: Mosaic-lower the full custom-vjp path ---
        def ssd_loss(fn, **kw):
            return lambda *a: jnp.sum(
                fn(*a, chunk_size=256, D=D, compute_dtype=jnp.float32, **kw)
                ** 2
            )

        g_ref = jax.jit(jax.grad(ssd_loss(ssd_chunked), (0, 1, 2, 3, 4)))(
            x, dt, A, B, C
        )
        g_pal = jax.jit(jax.grad(ssd_loss(ssd_chunked_pallas), (0, 1, 2, 3, 4)))(
            x, dt, A, B, C
        )
        jax.block_until_ready(g_pal)
        _progress("ssd pallas BACKWARD compiled+ran on hardware")
        for name, a, bb in zip("x dt A B C".split(), g_ref, g_pal):
            scale = float(jnp.max(jnp.abs(a))) or 1.0
            report(f"ssd_pallas_bwd_d{name}", bb / scale, a / scale, atol=2e-2)

        def m1_loss(fn):
            return lambda *a: jnp.sum(fn(*a, delta_softplus=True) ** 2)

        g_ref = jax.jit(jax.grad(m1_loss(selective_scan), (0, 1, 2, 3, 4)))(
            u, delta, A1, B1, C1
        )
        g_pal = jax.jit(jax.grad(m1_loss(selective_scan_pallas), (0, 1, 2, 3, 4)))(
            u, delta, A1, B1, C1
        )
        jax.block_until_ready(g_pal)
        _progress("m1 scan pallas BACKWARD compiled+ran on hardware")
        for name, a, bb in zip("u dt A B C".split(), g_ref, g_pal):
            scale = float(jnp.max(jnp.abs(a))) or 1.0
            report(f"m1_pallas_bwd_d{name}", bb / scale, a / scale, atol=2e-2)

        # --- seeded backwards (SP shards / decode prefill differentiate
        # through these): initial_state in, final-state cotangent seeding.
        # Shapes derive from the arrays (b/t/n were rebound by the m1
        # section above) ---
        s0 = jax.random.normal(
            jax.random.PRNGKey(7),
            (x.shape[0], x.shape[2], x.shape[3], C.shape[-1]),
        )

        def ssd_seeded_loss(fn):
            def inner(x, dt, A, B, C, s0):
                y, fin = fn(x, dt, A, B, C, chunk_size=256, D=D,
                            compute_dtype=jnp.float32, initial_state=s0,
                            return_final_state=True)
                return jnp.sum(y ** 2) + 0.5 * jnp.sum(fin ** 2)
            return inner

        g_ref = jax.jit(jax.grad(ssd_seeded_loss(ssd_chunked), (0, 5)))(
            x, dt, A, B, C, s0
        )
        g_pal = jax.jit(jax.grad(ssd_seeded_loss(ssd_chunked_pallas), (0, 5)))(
            x, dt, A, B, C, s0
        )
        jax.block_until_ready(g_pal)
        _progress("ssd pallas SEEDED backward compiled+ran on hardware")
        for name, a, bb in zip(("x", "initial_state"), g_ref, g_pal):
            scale = float(jnp.max(jnp.abs(a))) or 1.0
            report(f"ssd_pallas_seeded_bwd_d{name}", bb / scale, a / scale,
                   atol=2e-2)

        h0 = jax.random.normal(
            jax.random.PRNGKey(8),
            (u.shape[0], u.shape[2], A1.shape[-1]),
        )

        def m1_seeded_loss(fn):
            def inner(u, delta, A, B, C, h0):
                y, fin = fn(u, delta, A, B, C, delta_softplus=True,
                            initial_state=h0, return_final_state=True)
                return jnp.sum(y ** 2) + 0.5 * jnp.sum(fin ** 2)
            return inner

        g_ref = jax.jit(jax.grad(m1_seeded_loss(selective_scan), (0, 5)))(
            u, delta, A1, B1, C1, h0
        )
        g_pal = jax.jit(jax.grad(m1_seeded_loss(selective_scan_pallas), (0, 5)))(
            u, delta, A1, B1, C1, h0
        )
        jax.block_until_ready(g_pal)
        _progress("m1 pallas SEEDED backward compiled+ran on hardware")
        for name, a, bb in zip(("u", "initial_state"), g_ref, g_pal):
            scale = float(jnp.max(jnp.abs(a))) or 1.0
            report(f"m1_pallas_seeded_bwd_d{name}", bb / scale, a / scale,
                   atol=2e-2)

        # --- flash attention (hybrid layers), GQA shapes like config 5 ---
        from mamba_distributed_tpu.ops.blockwise_attention import (
            blockwise_sdpa_causal,
        )
        from mamba_distributed_tpu.ops.pallas.attention_kernels import (
            flash_sdpa_causal,
        )

        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        b, t, nh, nkv, hd = 2, 1024, 8, 2, 64
        q = jax.random.normal(ks[0], (b, t, nh, hd))
        kk = jax.random.normal(ks[1], (b, t, nkv, hd))
        vv = jax.random.normal(ks[2], (b, t, nkv, hd))
        ref = jax.jit(blockwise_sdpa_causal)(q, kk, vv)
        got = jax.jit(flash_sdpa_causal)(q, kk, vv)
        jax.block_until_ready(got)
        _progress("flash attention pallas compiled+ran on hardware")
        report("flash_attn_fwd_vs_blockwise", got, ref, atol=5e-3)

        def attn_loss(fn):
            return lambda *a: jnp.sum(fn(*a) ** 2)

        g_ref = jax.jit(jax.grad(attn_loss(blockwise_sdpa_causal), (0, 1, 2)))(
            q, kk, vv
        )
        g_pal = jax.jit(jax.grad(attn_loss(flash_sdpa_causal), (0, 1, 2)))(
            q, kk, vv
        )
        jax.block_until_ready(g_pal)
        _progress("flash attention BACKWARD compiled+ran on hardware")
        for name, a, bb in zip("q k v".split(), g_ref, g_pal):
            scale = float(jnp.max(jnp.abs(a))) or 1.0
            report(f"flash_attn_bwd_d{name}", bb / scale, a / scale, atol=2e-2)

    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
