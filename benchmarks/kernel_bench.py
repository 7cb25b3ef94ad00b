"""Kernel micro-benchmarks: wall-time of the production jnp paths on CPU plus
analytic TPU-roofline projections for the Pallas kernels (this container has
no TPU; the projection prices each kernel's FLOPs/bytes against v5e terms).

Each row stamps the active tuned config of the impl it measures
(``repro.tune`` winners installed via ``kernels.ops``; "" = shipped
defaults), so bench artifacts record *which* configuration produced each
number — comparable across runs that tuned differently.
"""
from __future__ import annotations

import json
import math
import time

import jax
import jax.numpy as jnp

from repro.hw.specs import TPU_V5E
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def _time(fn, *args, reps=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e3


def run(fast: bool = False) -> dict:
    rows = []
    chip = TPU_V5E

    # flash attention (train shape slice)
    B, S, Hq, Hkv, D = 1, 1024, 8, 4, 128
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
    fa_bk = int(ops.tuned_overrides("flash_attention", "chunked").get("block_k", 512))
    fa = jax.jit(lambda q, k, v: ref.flash_attention_chunked(q, k, v, causal=True,
                                                            block_k=fa_bk))
    ms = _time(fa, q, k, v, reps=5 if fast else 20)
    flops = 4 * B * Hq * D * S * (S + 1) / 2
    rows.append({
        "kernel": "flash_attention", "shape": f"B{B} S{S} H{Hq}/{Hkv} D{D}",
        "config": ops.active_config("flash_attention", "chunked"),
        "cpu_ms": round(ms, 2), "flops": flops,
        "tpu_compute_us": round(flops / chip.peak_flops_bf16 * 1e6, 1),
    })

    # local window attention
    swa = jax.jit(lambda q, k, v: ref.local_window_attention(q, k, v, window=256))
    ms2 = _time(swa, q, k, v, reps=5 if fast else 20)
    flops2 = 4 * B * Hq * D * (S * 256 - 256 * 255 / 2)
    rows.append({
        "kernel": "local_window_attention", "shape": f"S{S} w256",
        "config": ops.active_config("local_window_attention", "chunked"),
        "cpu_ms": round(ms2, 2), "flops": flops2,
        "tpu_compute_us": round(flops2 / chip.peak_flops_bf16 * 1e6, 1),
    })

    # gmm: ragged groups of uneven sizes over E experts (XLA's ragged_dot)
    E, M, Dm, F = 8, 2048, 512, 1024
    x = jax.random.normal(ks[0], (M, Dm), jnp.float32)
    w = jax.random.normal(ks[1], (E, Dm, F), jnp.float32)
    sizes = jnp.asarray([384, 128, 256, 0, 512, 256, 128, 384], jnp.int32)
    g = jax.jit(ref.gmm_ragged)
    ms3 = _time(g, x, w, sizes, reps=5 if fast else 20)
    flops3 = 2 * M * Dm * F
    rows.append({
        "kernel": "moe_gmm", "shape": f"E{E} M{M} D{Dm} F{F}",
        "config": ops.active_config("moe_gmm", "ref"),
        "cpu_ms": round(ms3, 2), "flops": flops3,
        "tpu_compute_us": round(flops3 / chip.peak_flops_bf16 * 1e6, 1),
    })

    # rwkv6 chunked
    B2, T, H, K = 1, 512, 8, 64
    r = jax.random.normal(ks[0], (B2, T, H, K))
    kk = jax.random.normal(ks[1], (B2, T, H, K))
    vv = jax.random.normal(ks[2], (B2, T, H, K))
    w6 = jnp.exp(-jnp.exp(jax.random.normal(ks[0], (B2, T, H, K)) * 0.3))
    u = jax.random.normal(ks[1], (H, K)) * 0.3
    s0 = jnp.zeros((B2, H, K, K))
    L = ops._scan_chunk("rwkv6_scan", "chunked", 32, T)
    rw = jax.jit(lambda *a: ref.rwkv6_scan_chunked(*a, chunk=L))
    ms4 = _time(rw, r, kk, vv, w6, u, s0, reps=3 if fast else 10)
    flops4 = B2 * H * T * (2 * L * K + 2 * L * K + 2 * K * K)  # att + intra + inter
    rows.append({
        "kernel": "rwkv6_scan", "shape": f"T{T} H{H} K{K} L{L}",
        "config": ops.active_config("rwkv6_scan", "chunked"),
        "cpu_ms": round(ms4, 2), "flops": flops4,
        "tpu_compute_us": round(flops4 / chip.peak_flops_bf16 * 1e6, 1),
    })

    # mamba chunked
    DI, N = 1024, 16
    x2 = jax.random.normal(ks[0], (B2, T, DI))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B2, T, DI)))
    A = -jnp.exp(jax.random.normal(ks[2], (DI, N)) * 0.3)
    Bm = jax.random.normal(ks[0], (B2, T, N))
    Cm = jax.random.normal(ks[1], (B2, T, N))
    Dp = jnp.ones((DI,))
    h0 = jnp.zeros((B2, DI, N))
    mchunk = ops._scan_chunk("mamba_scan", "chunked", 64, T)
    mb = jax.jit(lambda *a: ref.mamba_scan_chunked(*a, chunk=mchunk))
    ms5 = _time(mb, x2, dt, A, Bm, Cm, Dp, h0, reps=3 if fast else 10)
    bytes5 = B2 * T * (DI * 2 + N * 2) * 4 + B2 * T * DI * N * 4
    rows.append({
        "kernel": "mamba_scan", "shape": f"T{T} DI{DI} N{N}",
        "config": ops.active_config("mamba_scan", "chunked"),
        "cpu_ms": round(ms5, 2), "flops": B2 * T * DI * N * 10,
        "tpu_memory_us": round(bytes5 / chip.hbm_bw * 1e6, 1),
    })

    print(f"{'kernel':<24} {'shape':<22} {'config':<14} {'cpu_ms':>8} {'tpu_proj_us':>11}")
    for row in rows:
        proj = row.get("tpu_compute_us", row.get("tpu_memory_us", 0))
        print(f"{row['kernel']:<24} {row['shape']:<22} "
              f"{row.get('config') or '(defaults)':<14} "
              f"{row['cpu_ms']:>8.2f} {proj:>11.1f}")
    return {"rows": rows}


def main() -> None:
    rec = run()
    with open("benchmarks/out_kernels.json", "w") as f:
        json.dump(rec, f, indent=1)


if __name__ == "__main__":
    main()
