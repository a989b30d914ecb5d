"""Kernel microbenchmarks: wall time of the jnp reference vs the Pallas
kernel (interpret mode on CPU — the timing is indicative only; the real
target is TPU Mosaic, see kernels/*.py docstrings).

``--payload`` runs the payload-scale suite instead: the fused
quantize->pack->dequant-aggregate pipeline at N=256 devices, d=10^6
(full mode adds d=10^7) against the materialize-then-sum baseline, with
per-kernel achieved bytes/s and FLOP/s vs the ``benchmarks.roofline``
peaks, and the bf16-payload/f32-accumulate kernel rows. Writes the schema-stamped record to
the repo-root ``BENCH_kernel_payload.json`` (tracked across PRs, next to
``BENCH_engine_scale.json``). ``--rss-budget-mb`` guards the fused
phase's peak RSS (exit 1 on overrun — the scripts/verify.sh CI gate that
pins the O(d) aggregation claim)."""
from __future__ import annotations

import argparse
import resource
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.kernels.payload import unpack_dequant_rows_2d
from repro import compile_cache


def _time(fn, *args, reps=3):
    fn(*args)            # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def run(quick: bool = True):
    rows = []
    n = 1 << (18 if quick else 22)
    g = jax.random.normal(jax.random.key(0), (n,))
    key = jax.random.key(1)

    f_ref = jax.jit(lambda g: ops.dithered_quantize(g, 255.0, key,
                                                    use_kernel=False))
    f_ker = jax.jit(lambda g: ops.dithered_quantize(g, 255.0, key,
                                                    use_kernel=True))
    rows.append(("kernel/dithered_quant/ref", _time(f_ref, g), f"n={n}"))
    rows.append(("kernel/dithered_quant/pallas-interp", _time(f_ker, g),
                 f"n={n}"))

    a = jnp.asarray(3.0)
    ns = jnp.asarray(0.1)
    f_ref = jax.jit(lambda g: ops.ota_combine(g, a, ns, key,
                                              use_kernel=False))
    f_ker = jax.jit(lambda g: ops.ota_combine(g, a, ns, key,
                                              use_kernel=True))
    rows.append(("kernel/ota_combine/ref", _time(f_ref, g), f"n={n}"))
    rows.append(("kernel/ota_combine/pallas-interp", _time(f_ker, g),
                 f"n={n}"))

    B, S, D = 2, 512 if quick else 2048, 256
    aa = jax.random.uniform(jax.random.key(2), (B, S, D), minval=.5,
                            maxval=.99)
    bb = jax.random.normal(jax.random.key(3), (B, S, D)) * .1
    h0 = jnp.zeros((B, D))
    f_ref = jax.jit(lambda a, b, h: ops.linear_scan(a, b, h,
                                                    use_kernel=False))
    f_ker = jax.jit(lambda a, b, h: ops.linear_scan(a, b, h,
                                                    use_kernel=True))
    rows.append(("kernel/linear_scan/ref", _time(f_ref, aa, bb, h0),
                 f"B{B}xS{S}xD{D}"))
    rows.append(("kernel/linear_scan/pallas-interp",
                 _time(f_ker, aa, bb, h0), f"B{B}xS{S}xD{D}"))
    return rows, {}


# ------------------------------------------------- payload-scale suite

def _rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def _time_s(fn, *args, reps=2):
    jax.block_until_ready(fn(*args))     # compile / warm
    best = np.inf
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _roofline_fracs(bytes_moved: float, flops: float, secs: float) -> dict:
    """Achieved throughput vs the roofline peaks (indicative on CPU
    interpret; the fractions become meaningful on TPU Mosaic)."""
    from .roofline import HBM_BW, PEAK_FLOPS
    return {
        "bytes": bytes_moved, "flops": flops, "wall_s": secs,
        "achieved_bytes_per_s": bytes_moved / secs,
        "achieved_flops_per_s": flops / secs,
        "frac_hbm_bw": bytes_moved / secs / HBM_BW,
        "frac_peak_flops": flops / secs / PEAK_FLOPS,
    }


def _payload_case(n_dev: int, d: int, r_bits: int, seed: int = 0,
                  chunk: int = 16) -> dict:
    """One (N, d) payload-scale measurement: fused vs materialize-then-sum.

    Device gradients come from ``SyntheticHighDimTask`` (O(d) closed form)
    and are packed in ``chunk``-device slices, so the full (N, d) float
    gradient block never exists host- or device-side — only the uint32
    payload buffer (code_bits/32 of the float bytes) plus one in-flight
    chunk. The fused phase runs FIRST: ru_maxrss is a monotone high-water
    mark, so its reading excludes the baseline's (N, d) materialization.
    """
    from repro.fl.tasks import SyntheticHighDimTask

    cb = ops.code_bits_for(r_bits)
    task = SyntheticHighDimTask(d, seed=seed)
    w32 = jnp.zeros(d, jnp.float32)
    levels = jnp.full(n_dev, float(2 ** r_bits - 1), jnp.float32)
    key = jax.random.PRNGKey(seed + 1)

    t0 = time.perf_counter()
    words_parts, scal_parts = [], []
    pk = None
    for c0 in range(0, n_dev, chunk):
        c = min(chunk, n_dev - c0)
        xs = jnp.arange(c0, c0 + c, dtype=jnp.float32).reshape(c, 1, 1)
        ys = jnp.zeros((c, 1), jnp.int32)
        g = task.device_grads_fn(w32, xs, ys)
        u = jax.random.uniform(jax.random.fold_in(key, c0), g.shape,
                               dtype=jnp.float32)
        pk = ops.quantize_pack(g, levels[c0:c0 + c], u, code_bits=cb)
        words_parts.append(pk.words)
        scal_parts.append(pk.scal)
    words = jnp.concatenate(words_parts)
    scal = jnp.concatenate(scal_parts)
    jax.block_until_ready(words)
    del words_parts, scal_parts
    pack_s = time.perf_counter() - t0
    block_rows = pk.block_rows
    d_padded = words.shape[0] * (32 // cb) * 128 // n_dev
    wvec = jnp.full(n_dev, 1.0 / n_dev, jnp.float32)

    def fused_fn(wd, wv):
        return ops.packed_weighted_sum(
            ops.PackedGrads(wd, scal, cb, n_dev, d, block_rows), wv)

    fused_j = jax.jit(fused_fn)
    t_fused = _time_s(fused_j, words, wvec)
    fused_rss = _rss_mb()

    # materialize-then-sum baseline: same Pallas unpack technology, then a
    # weighted matvec over the (N, d) float block. The matvec runs on the
    # padded width and slices the (d,) result — slicing the matrix first
    # would copy another N*d floats.
    interp = jax.default_backend() == "cpu"

    def base_fn(wd, wv):
        gq = unpack_dequant_rows_2d(wd, scal, code_bits=cb, n_dev=n_dev,
                                    interpret=interp, block_rows=block_rows)
        return (wv @ gq.reshape(n_dev, -1))[:d]

    base_j = jax.jit(base_fn)
    t_base = _time_s(base_j, words, wvec)
    base_rss = _rss_mb()
    dev = float(jnp.max(jnp.abs(fused_j(words, wvec) - base_j(words, wvec))))

    payload_bytes = n_dev * d_padded * cb / 8
    # fused: read every packed word once, write the (d,) accumulator
    fused_roof = _roofline_fracs(payload_bytes + d_padded * 4,
                                 3.0 * n_dev * d_padded, t_fused)
    # baseline: read packed words, write + re-read the (N, d) float block,
    # write the accumulator
    base_roof = _roofline_fracs(payload_bytes + 2 * n_dev * d_padded * 4
                                + d_padded * 4,
                                4.0 * n_dev * d_padded, t_base)
    return {
        "n_devices": n_dev, "dim": d, "dim_padded": int(d_padded),
        "r_bits": r_bits, "code_bits": cb, "block_rows": int(block_rows),
        "packed_mb": words.nbytes / 2 ** 20,
        "materialized_mb": n_dev * d_padded * 4 / 2 ** 20,
        "pack_wall_s": pack_s,
        "fused": {**fused_roof, "peak_rss_mb": fused_rss},
        "baseline": {**base_roof, "peak_rss_mb": base_rss},
        "speedup": t_base / t_fused,
        "max_abs_deviation": dev,
    }


def _bf16_kernel_rows(d: int) -> list:
    """bf16-payload / f32-accumulate kernel rows vs the f32/f32 kernels."""
    key = jax.random.PRNGKey(3)
    g32 = jax.random.normal(key, (d,), jnp.float32)
    g16 = g32.astype(jnp.bfloat16)
    z = jax.random.normal(jax.random.fold_in(key, 1), (d,), jnp.float32)
    alpha = jnp.asarray(3.0)
    rows = []

    ota32 = jax.jit(lambda g: ops.ota_combine_with_noise(g, alpha, z))
    ota16 = jax.jit(lambda g: ops.ota_combine_with_noise(
        g, alpha, z, acc_dtype=jnp.float32))
    t32, t16 = _time_s(ota32, g32), _time_s(ota16, g16)
    err = float(jnp.max(jnp.abs(ota16(g16) - ota32(g32))))
    rows.append({"kernel": "ota_combine", "dim": d, "f32_s": t32,
                 "bf16_payload_s": t16, "payload_bytes_ratio": 0.5,
                 "max_abs_deviation": err})

    red32 = jax.jit(lambda g: ops.row_maxabs_sumsq(g[None, :]))
    red16 = jax.jit(lambda g: ops.row_maxabs_sumsq(g[None, :]))
    t32, t16 = _time_s(red32, g32), _time_s(red16, g16)
    m32, s32 = red32(g32)
    m16, s16 = red16(g16)
    rel = float(jnp.abs(s16[0] - s32[0]) / s32[0])
    rows.append({"kernel": "row_maxabs_sumsq", "dim": d, "f32_s": t32,
                 "bf16_payload_s": t16, "payload_bytes_ratio": 0.5,
                 "sumsq_rel_deviation": rel})
    return rows


def run_payload(quick: bool = True, *, rss_budget_mb=None):
    """Payload-scale fused-pipeline benchmark -> BENCH_kernel_payload.json.

    Measures the fused digital path (dither->quantize->bit-pack into a
    uint32 payload buffer, then unpack-dequant-weighted-accumulate with an
    O(d) accumulator) against materialize-then-sum at N=256 devices,
    d=10^6 — the regime where the (N, d) float block is a gigabyte that
    exists only to be summed. Full mode adds a d=10^7 point at N=32.
    Also records the bf16-payload/f32-accumulate kernel rows, all
    schema-stamped to the repo-root ``BENCH_kernel_payload.json``.
    """
    from .common import dump_json, result_payload

    cases = [_payload_case(256, 1_000_000, 8)]
    if not quick:
        cases.append(_payload_case(32, 10_000_000, 8, chunk=4))
    bf16 = _bf16_kernel_rows(1_000_000)
    payload = result_payload(
        "kernel_bench_payload", quick=quick, cases=cases,
        bf16_kernels=bf16, rss_budget_mb=rss_budget_mb)
    out = Path(__file__).resolve().parents[1] / "BENCH_kernel_payload.json"
    out.write_text(dump_json(payload))
    rows = []
    for c in cases:
        rows.append((f"kernel_payload/N{c['n_devices']}_d{c['dim']}/fused",
                     c["fused"]["wall_s"] * 1e6,
                     f"speedup={c['speedup']:.2f}x;"
                     f"rss={c['fused']['peak_rss_mb']:.0f}MB"))
        rows.append((f"kernel_payload/N{c['n_devices']}_d{c['dim']}/baseline",
                     c["baseline"]["wall_s"] * 1e6,
                     f"rss={c['baseline']['peak_rss_mb']:.0f}MB"))
    return rows, payload


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--payload", action="store_true",
                    help="payload-scale fused-pipeline suite (writes "
                         "top-level BENCH_kernel_payload.json)")
    ap.add_argument("--smoke", action="store_true",
                    help="with --payload: keep the quick N=256, d=1e6 case "
                         "only (the CI gate size)")
    ap.add_argument("--full", action="store_true",
                    help="with --payload: add the d=1e7 case")
    ap.add_argument("--rss-budget-mb", type=float, default=None,
                    help="with --payload: exit 1 if the FUSED phase's peak "
                         "RSS exceeds this (the O(d) aggregation guard)")
    args = ap.parse_args()
    compile_cache.enable()
    if not args.payload:
        rows, _ = run(quick=True)
        for r in rows:
            print(f"{r[0]},{r[1]:.1f},{r[2]}")
        return
    rows, payload = run_payload(quick=not args.full,
                                rss_budget_mb=args.rss_budget_mb)
    for c in payload["cases"]:
        f, b = c["fused"], c["baseline"]
        print(f"N={c['n_devices']} d={c['dim']} ({c['code_bits']}-bit codes, "
              f"tile {c['block_rows']}): packed {c['packed_mb']:.0f} MB vs "
              f"materialized {c['materialized_mb']:.0f} MB")
        print(f"  fused    {f['wall_s']:.2f}s  RSS {f['peak_rss_mb']:.0f} MB"
              f"  ({f['achieved_bytes_per_s'] / 1e9:.2f} GB/s, "
              f"{f['frac_hbm_bw'] * 100:.2f}% of TPU HBM roofline)")
        print(f"  baseline {b['wall_s']:.2f}s  RSS {b['peak_rss_mb']:.0f} MB"
              f"  -> fused speedup {c['speedup']:.2f}x, "
              f"max deviation {c['max_abs_deviation']:.1e}")
    for r in payload["bf16_kernels"]:
        print(f"bf16 {r['kernel']} d={r['dim']}: f32 {r['f32_s'] * 1e3:.1f}ms"
              f" vs bf16-payload {r['bf16_payload_s'] * 1e3:.1f}ms "
              f"(half the payload bytes)")
    print(f"-> BENCH_kernel_payload.json")
    gate = payload["cases"][0]
    if (args.rss_budget_mb is not None
            and gate["fused"]["peak_rss_mb"] > args.rss_budget_mb):
        print(f"FAIL: fused-phase peak RSS {gate['fused']['peak_rss_mb']:.0f}"
              f" MB exceeds budget {args.rss_budget_mb:.0f} MB — is the "
              "(N, d) dequantized block materialized on the fused path?",
              file=sys.stderr)
        sys.exit(1)
    if gate["speedup"] < 1.0 or (gate["fused"]["peak_rss_mb"]
                                 >= gate["baseline"]["peak_rss_mb"]):
        print("FAIL: fused path must beat materialize-then-sum in both "
              f"wall-clock (speedup {gate['speedup']:.2f}x) and peak RSS "
              f"({gate['fused']['peak_rss_mb']:.0f} vs "
              f"{gate['baseline']['peak_rss_mb']:.0f} MB)", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
