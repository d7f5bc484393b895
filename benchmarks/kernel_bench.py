"""Mask-uplink kernel microbenchmark: fused vs staged pipeline.

The client hot loop used to run the uplink as three separately dispatched
programs — PSM sample (f32 mask tree), bitpack (uint32 words), and the
server-side popcount (words → int8 bits → counts, a 32× re-expansion).
``mask_uplink_fused`` does all three in one pass, emitting packed words
and per-block count/weighted-sum partials directly, so the f32 mask tree
and the unpacked bit tensor never round-trip through HBM.

Rows (derived = calls/sec unless stated):
  kernels/uplink/<mode>/staged    sample → pack → unpack-counts (+ the
                                  Σ_k w_k n_k⊙m_k aggregate) as separate
                                  jitted dispatches, as the legacy route
                                  runs them
  kernels/uplink/<mode>/fused     one ``mask_uplink_fused`` program on
                                  the DEFAULT backend (pallas on TPU,
                                  the jnp oracle elsewhere)
  kernels/uplink/<mode>/speedup   staged/fused wall-time ratio — the
                                  acceptance row (>= 1.3x)
  kernels/apply/staged            server update as unpack-counts then
                                  ``w + n*(s*c)`` (two dispatches)
  kernels/apply/fused             one ``unpack_counts_apply`` program
  kernels/apply/speedup           staged/fused ratio

Analytic roofline rows (derived = bytes; the memory term of the
three-term roofline model, counting HBM traffic of each pipeline):
  kernels/roofline/<mode>/hbm_staged_B
  kernels/roofline/<mode>/hbm_fused_B
  kernels/roofline/<mode>/hbm_ratio   staged/fused — the memory-term
                                      delta the fusion buys

``write_bench_json`` emits the machine-readable ``BENCH_kernels.json``
next to the repo root (same trajectory-tracking idiom as
``BENCH_engine.json``).
"""
from __future__ import annotations

import json
import os
import subprocess
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backend import resolve_backend
from repro.core.packing import pack_rows, unpack_rows
from repro.kernels.mask_uplink import ops as mops

# full sizes: K clients x 1M params — the regime of the paper's CNN;
# smoke mode (CI) shrinks P so the whole section runs in seconds.
K_FULL, P_FULL = 8, 1 << 20
K_SMOKE, P_SMOKE = 4, 1 << 16

BENCH_JSON = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_kernels.json")

_EPS = 1e-30


def _time_calls(call, repeats: int = 3, n: int = 5) -> float:
    """Best-of-``repeats`` wall-seconds per call after a compile/warmup
    call (same idiom as engine_bench._time_rounds)."""
    jax.block_until_ready(call())
    best = float("inf")
    for _ in range(repeats):
        t0 = time.time()
        out = None
        for _ in range(n):
            out = call()
        jax.block_until_ready(out)
        best = min(best, (time.time() - t0) / n)
    return best


def _operands(K: int, P: int):
    ku, kn, kr = jax.random.split(jax.random.key(0), 3)
    u = 0.01 * jax.random.normal(ku, (K, P))
    n = 0.01 * jax.random.normal(kn, (K, P))
    r = jax.random.uniform(kr, (K, P))
    w = jnp.linspace(0.5, 1.5, K)
    return u, n, r, w


def _staged_fns(mode: str, P: int):
    """The legacy pipeline as separately jitted stages (each one is a
    real dispatch boundary in the legacy route: mask tree and bit tensor
    round-trip through HBM between them)."""

    @jax.jit
    def sample(u, n, r):
        safe = jnp.where(jnp.abs(n) < _EPS, _EPS, n)
        if mode == "signed":
            p = jnp.clip((u + n) / (2.0 * safe), 0.0, 1.0)
        else:
            p = jnp.clip(u / safe, 0.0, 1.0)
        return (r < p).astype(jnp.int8)

    pack = jax.jit(lambda m: pack_rows(m, backend="ref"))

    @jax.jit
    def counts(words):
        bits = unpack_rows(words, P, backend="ref")   # the 32x expansion
        return jnp.sum(bits, axis=0, dtype=jnp.int32)

    @jax.jit
    def wsum(w, n, m):
        if mode == "signed":
            hat = jnp.where(m.astype(bool), n, -n)
        else:
            hat = jnp.where(m.astype(bool), n, 0.0)
        return jnp.tensordot(w, hat, axes=1)

    return sample, pack, counts, wsum


def uplink_rows(K: int, P: int) -> List[Dict]:
    backend = resolve_backend(None)
    use_pallas = backend == "pallas"
    u, n, r, w = _operands(K, P)
    rows = []
    for mode in ("binary", "signed"):
        sample, pack, counts, wsum = _staged_fns(mode, P)

        def staged():
            m = sample(u, n, r)
            words = pack(m)
            c = counts(words)
            s = wsum(w, n, m)
            return words, c, s

        fused_fn = jax.jit(lambda u, n, r, w: mops.mask_uplink_fused(
            u, n, r, None, None, w, mode=mode, use_pallas=use_pallas))

        def fused():
            return fused_fn(u, n, r, w)

        t_staged = _time_calls(staged)
        t_fused = _time_calls(fused)
        rows += [
            dict(name=f"kernels/uplink/{mode}/staged",
                 us_per_call=t_staged * 1e6,
                 derived=round(1.0 / t_staged, 2)),
            dict(name=f"kernels/uplink/{mode}/fused",
                 us_per_call=t_fused * 1e6,
                 derived=round(1.0 / t_fused, 2)),
            dict(name=f"kernels/uplink/{mode}/speedup", us_per_call=0.0,
                 derived=round(t_staged / t_fused, 2)),
        ] + _roofline_rows(mode, K, P)
    return rows


def _roofline_rows(mode: str, K: int, P: int) -> List[Dict]:
    """Analytic HBM traffic (bytes) of each pipeline — the memory term
    of the roofline model.  Staged stages are separate programs, so
    every intermediate is an HBM round-trip; the fused kernel stages
    everything through VMEM and only the wire words + per-block partial
    sums ever hit HBM."""
    f32, i8, u32 = 4, 1, 4
    words_B = (P // 32 + (1 if P % 32 else 0)) * u32 * K
    # staged: sample(rd u,n,r; wr mask) + pack(rd mask; wr words)
    #       + counts(rd words; wr bits; rd bits; wr counts)
    #       + wsum(rd n, mask; wr hat is fused into the tensordot: rd only)
    staged = (3 * K * P * f32 + K * P * i8            # sample
              + K * P * i8 + words_B                  # pack
              + words_B + 2 * K * P * i8 + P * 4      # unpack + popcount
              + K * P * (f32 + i8) + P * f32)         # weighted aggregate
    # fused: rd u,n,r once; wr words + count/wsum partials (gr rows each)
    gr = max(1, -(-K // 8))                            # K/8 row blocks
    fused = 3 * K * P * f32 + words_B + 2 * gr * P * 4
    return [
        dict(name=f"kernels/roofline/{mode}/hbm_staged_B", us_per_call=0.0,
             derived=staged),
        dict(name=f"kernels/roofline/{mode}/hbm_fused_B", us_per_call=0.0,
             derived=fused),
        dict(name=f"kernels/roofline/{mode}/hbm_ratio", us_per_call=0.0,
             derived=round(staged / fused, 2)),
    ]


def apply_rows(K: int, P: int) -> List[Dict]:
    """Server side: words → counts → global-model update."""
    backend = resolve_backend(None)
    use_pallas = backend == "pallas"
    u, n, r, _ = _operands(K, P)
    m = (r < jnp.clip(u / jnp.where(jnp.abs(n) < _EPS, _EPS, n), 0, 1))
    words = jax.jit(lambda m: pack_rows(m.astype(jnp.int8),
                                        backend="ref"))(m)
    base = jnp.zeros((P,))
    scale = 1.0 / K

    unpack = jax.jit(lambda ws: jnp.sum(
        unpack_rows(ws, P, backend="ref"), axis=0, dtype=jnp.int32))
    apply_ = jax.jit(lambda c: base + n[0] * (scale * c.astype(jnp.float32)))

    def staged():
        return apply_(unpack(words))

    fused_fn = jax.jit(lambda ws: mops.unpack_counts_apply(
        ws, n[0], base, scale, 1.0, 0.0, use_pallas=use_pallas))

    def fused():
        return fused_fn(words)

    t_staged = _time_calls(staged)
    t_fused = _time_calls(fused)
    return [
        dict(name="kernels/apply/staged", us_per_call=t_staged * 1e6,
             derived=round(1.0 / t_staged, 2)),
        dict(name="kernels/apply/fused", us_per_call=t_fused * 1e6,
             derived=round(1.0 / t_fused, 2)),
        dict(name="kernels/apply/speedup", us_per_call=0.0,
             derived=round(t_staged / t_fused, 2)),
    ]


def kernel_rows(smoke: bool = False) -> List[Dict]:
    K, P = (K_SMOKE, P_SMOKE) if smoke else (K_FULL, P_FULL)
    return uplink_rows(K, P) + apply_rows(K, P)


def write_bench_json(rows: List[Dict], path: str = BENCH_JSON,
                     smoke: bool = False) -> str:
    """Emit machine-readable kernel results (bench trajectory idiom)."""
    try:
        commit = subprocess.check_output(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            text=True).strip()
    except Exception:  # noqa: BLE001 — no git in CI tarballs
        commit = "unknown"
    K, P = (K_SMOKE, P_SMOKE) if smoke else (K_FULL, P_FULL)
    results: Dict[str, Dict] = {}
    for r in rows:
        if r["name"].startswith("kernels/"):
            key = "/".join(r["name"].split("/")[1:-1])
            results.setdefault(key, {})[r["name"].split("/")[-1]] = (
                r["derived"])
    doc = {
        "bench": "kernels",
        "commit": commit,
        "config": {"clients": K, "params": P, "smoke": smoke,
                   "backend": resolve_backend(None),
                   "n_devices": jax.local_device_count(),
                   "unit": "calls_per_sec (speedup/hbm_ratio rows are "
                           "ratios; hbm_*_B rows are analytic bytes)"},
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "results": results,
    }
    path = os.path.abspath(path)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    return path


if __name__ == "__main__":
    import sys
    smoke = "--smoke" in sys.argv
    print("name,us_per_call,derived")
    all_rows = kernel_rows(smoke=smoke)
    for row in all_rows:
        print(f"{row['name']},{row['us_per_call']:.1f},{row['derived']}")
    print(f"# wrote {write_bench_json(all_rows, smoke=smoke)}",
          file=sys.stderr)
