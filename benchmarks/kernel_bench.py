"""Aggregation-kernel microbenchmark (``BENCH_kernel.json``): what do the
Pallas fusion kernels cost, and what does autotuning the tile sizes buy?

On this CPU container the kernels run in interpret mode (not representative
of TPU throughput), so the benchmark reports two kinds of rows:

  model rows     closed-form and fully deterministic: per (kernel, K, N),
                 the corrected HBM bytes moved and the bandwidth-roofline /
                 modeled TPU v5e time at the kernel's built-in default tile
                 vs the autotuned tile (`repro.kernels.autotune`). The old
                 derivation here was ``bytes = (k*n + n)*4`` — it ignored
                 the fp32 output tile's read-modify-write on every K-grid
                 revisit (``o_ref[...] +=``) and padding, undercounting
                 traffic for every multi-K-slab launch.
  measured rows  interpret-mode wall-clock of default vs tuned tile on
                 small shapes. Interpret mode executes the kernel body once
                 per grid step in Python, so time tracks grid steps — the
                 tuned/default *ratio* is a stable, hardware-portable
                 signal that the tuner actually reduces grid traffic, even
                 though the absolute numbers mean nothing for TPU. Timing
                 discipline: warmup call blocked before the first trial
                 (async dispatch would bleed compile+execute into trial 0),
                 median of >= 3 trials everywhere.

  python -m benchmarks.kernel_bench [--check BASELINE] [--out OUT]
                                    [--emit-cost-table PATH]

--check mirrors ``benchmarks/simcore.py``: deterministic columns (tile
choices, bytes, grid steps, modeled speedup) must match the committed
``benchmarks/kernel_baseline.json`` exactly, and each measured
tuned-vs-default speedup must hold at >= 70% of the baseline's ratio — a
RATIO guard, portable across CI hardware. The committed baseline ratios
are deliberately conservative (below the lowest speedup observed across
repeated runs, not a single lucky measurement) because interpret-mode
timing is load-sensitive. --emit-cost-table additionally
writes the `KernelCostTable` artifact the estimator consumes
(``AggregationEstimator(cost_table=...)``, ``Platform(cost_table=...)``).

CSV: see MODEL_HEADER / MEASURED_HEADER.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.autotune import (KERNELS, autotune, build_cost_table,
                                    grid_steps, kernel_bytes_moved,
                                    modeled_time_s)
from repro.kernels.fused_agg import fused_agg
from repro.kernels.ops import interpret_mode
from repro.kernels.pair_fuse import pair_fuse
from repro.kernels.quant_agg import quant_agg
from repro.launch.roofline import bandwidth_time_s

#: model rows — closed-form, any size is free
MODEL_CASES: Tuple[Tuple[str, int, int], ...] = (
    ("fused_agg", 8, 1 << 20),
    ("fused_agg", 32, 1 << 20),
    ("fused_agg", 8, 1 << 22),
    ("quant_agg", 32, 1 << 20),
    ("quant_agg", 64, 1 << 22),
    ("pair_fuse", 2, 1 << 20),
    ("pair_fuse", 2, 1 << 22),
)
#: measured rows — interpret mode executes the kernel body per grid step in
#: Python; keep the timed shapes small (the RATIO is the signal)
MEASURED_CASES: Tuple[Tuple[str, int, int], ...] = (
    ("fused_agg", 8, 1 << 16),
    ("quant_agg", 32, 1 << 16),
    # pair_fuse is so cheap per step that a 64k case times in the noise
    # floor; 512k keeps the tuned/default ratio stable (32 vs 16 steps)
    ("pair_fuse", 2, 1 << 19),
)

#: --check: fail if a measured speedup falls below this fraction of the
#: committed baseline's (hardware-portable ratio guard, like simcore)
CHECK_SPEEDUP_FRACTION = 0.7

MODEL_HEADER = ("kernel,k,n,default_bn,default_kb,tuned_bn,tuned_kb,"
                "bytes_default,bytes_tuned,steps_default,steps_tuned,"
                "tpu_roofline_us_default,tpu_roofline_us_tuned,"
                "modeled_us_default,modeled_us_tuned,modeled_speedup")
MEASURED_HEADER = ("kernel,k,n,us_ref_cpu,us_default,us_tuned,"
                   "measured_speedup")


def timeit(fn, *args, trials: int = 3) -> float:
    """Median microseconds per call; warmup blocked, trials >= 3."""
    trials = max(trials, 3)
    jax.block_until_ready(fn(*args))  # warmup: compile AND drain async work
    ts = []
    for _ in range(trials):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e6  # us


def _inputs(kernel: str, k: int, n: int):
    key = jax.random.PRNGKey(0)
    if kernel == "fused_agg":
        u = jax.random.normal(key, (k, n), jnp.float32)
        w = jnp.full((k,), 1.0 / k, jnp.float32)
        return u, w
    if kernel == "quant_agg":
        q = jax.random.randint(key, (k, n), -127, 128, dtype=jnp.int8)
        s = jnp.full((k,), 0.01, jnp.float32)
        return q, s
    a = jax.random.normal(key, (n,), jnp.float32)
    return a, a


def _call(kernel: str, bn: int, kb: int):
    if kernel == "fused_agg":
        return lambda u, w: fused_agg(u, w, bn=bn, kb=kb,
                                      interpret=interpret_mode())
    if kernel == "quant_agg":
        return lambda q, s: quant_agg(q, s, bn=bn, kb=kb,
                                      interpret=interpret_mode())
    return lambda a, b: pair_fuse(a, b, op="wsum", wa=0.5, wb=0.5,
                                  bn=bn, interpret=interpret_mode())


def _ref_call(kernel: str):
    if kernel == "fused_agg":
        return jax.jit(ref.fused_agg_ref)
    if kernel == "quant_agg":
        return jax.jit(ref.quant_agg_ref)
    return jax.jit(lambda a, b: ref.pair_fuse_ref(a, b, op="wsum",
                                                  wa=0.5, wb=0.5))


def model_rows() -> List[Dict]:
    rows = []
    for kernel, k, n in MODEL_CASES:
        spec = KERNELS[kernel]
        dbn, dkb = spec.default_bn, spec.default_kb
        tuned = autotune(kernel, k, n)
        b_def = kernel_bytes_moved(kernel, k, n, bn=dbn, kb=dkb)
        m_def = modeled_time_s(kernel, k, n, bn=dbn, kb=dkb)
        m_tun = tuned.modeled_s
        rows.append({
            "kernel": kernel, "k": k, "n": n,
            "default_bn": dbn, "default_kb": dkb,
            "tuned_bn": tuned.bn, "tuned_kb": tuned.kb,
            "bytes_default": b_def, "bytes_tuned": tuned.bytes_moved,
            "steps_default": grid_steps(kernel, k, n, bn=dbn, kb=dkb),
            "steps_tuned": grid_steps(kernel, k, n, bn=tuned.bn,
                                      kb=tuned.kb),
            "tpu_roofline_us_default": round(
                bandwidth_time_s(b_def) * 1e6, 3),
            "tpu_roofline_us_tuned": round(tuned.roofline_s * 1e6, 3),
            "modeled_us_default": round(m_def * 1e6, 3),
            "modeled_us_tuned": round(m_tun * 1e6, 3),
            "modeled_speedup": round(m_def / m_tun, 3),
        })
    return rows


def measured_rows() -> List[Dict]:
    rows = []
    for kernel, k, n in MEASURED_CASES:
        spec = KERNELS[kernel]
        args = _inputs(kernel, k, n)
        tuned = autotune(kernel, k, n)
        us_ref = timeit(_ref_call(kernel), *args)
        us_def, us_tun, speedup = _paired(
            _call(kernel, spec.default_bn, spec.default_kb),
            _call(kernel, tuned.bn, tuned.kb), *args)
        rows.append({
            "kernel": kernel, "k": k, "n": n,
            "us_ref_cpu": round(us_ref, 1),
            "us_default": round(us_def, 1),
            "us_tuned": round(us_tun, 1),
            "measured_speedup": round(speedup, 2),
        })
    return rows


def _paired(f_def, f_tun, *args, trials: int = 5):
    """(median us of f_def, median us of f_tun, median per-trial ratio).

    Each trial times the two back to back, so load from other processes
    that comes and goes within a run weighs on both sides of the ratio."""
    jax.block_until_ready(f_def(*args))  # warmup: compile AND drain
    jax.block_until_ready(f_tun(*args))
    t_def, t_tun = [], []
    for _ in range(trials):
        for fn, ts in ((f_def, t_def), (f_tun, t_tun)):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts.append(time.perf_counter() - t0)
    ratio = np.median(np.asarray(t_def) / np.asarray(t_tun))
    return (float(np.median(t_def)) * 1e6, float(np.median(t_tun)) * 1e6,
            float(ratio))


def speedups(measured: List[Dict]) -> Dict[str, float]:
    return {f"{r['kernel']}_k{r['k']}_n{r['n']}": r["measured_speedup"]
            for r in measured}


def run() -> Tuple[List[Dict], List[Dict], Dict[str, float]]:
    print(MODEL_HEADER)
    model = model_rows()
    for r in model:
        print(",".join(str(v) for v in r.values()), flush=True)
    print(MEASURED_HEADER)
    measured = measured_rows()
    for r in measured:
        print(",".join(str(v) for v in r.values()), flush=True)
    sp = speedups(measured)
    for name, s in sp.items():
        print(f"[interpret speedup {name}: {s}x tuned vs default]")
    return model, measured, sp


#: deterministic model-row columns the baseline locks exactly
DETERMINISTIC_COLS = ("default_bn", "default_kb", "tuned_bn", "tuned_kb",
                      "bytes_default", "bytes_tuned", "steps_default",
                      "steps_tuned", "modeled_speedup")


def check_against(baseline_path: str, model: List[Dict],
                  sp: Dict[str, float]) -> None:
    """Regression guard vs a committed baseline: tile choices / modeled
    traffic exact, measured interpret speedups within
    CHECK_SPEEDUP_FRACTION of the baseline ratio."""
    with open(baseline_path) as f:
        base = json.load(f)
    base_by = {(r["kernel"], r["k"], r["n"]): r for r in base["model_rows"]}
    failures: List[str] = []
    for r in model:
        b = base_by.get((r["kernel"], r["k"], r["n"]))
        if b is None:
            continue
        for col in DETERMINISTIC_COLS:
            if r[col] != b[col]:
                failures.append(
                    f"{r['kernel']}/k{r['k']}/n{r['n']}: {col} {r[col]} != "
                    f"baseline {b[col]} (tuning/model drift)")
    for name, got in sp.items():
        want = base.get("speedups", {}).get(name)
        if want is None:
            continue
        floor = CHECK_SPEEDUP_FRACTION * want
        if got < floor:
            failures.append(
                f"{name}: measured speedup {got}x < {floor:.2f}x "
                f"(>{100 * (1 - CHECK_SPEEDUP_FRACTION):.0f}% drop vs "
                f"baseline {want}x)")
    if failures:
        print("[kernel regression check FAILED]", file=sys.stderr)
        for msg in failures:
            print("  " + msg, file=sys.stderr)
        raise SystemExit(1)
    print(f"[kernel regression check OK vs {baseline_path}]")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--check", default="",
                    help="baseline JSON to regression-check against")
    ap.add_argument("--out", default="BENCH_kernel.json",
                    help="write rows as JSON here ('' to skip)")
    ap.add_argument("--emit-cost-table", default="",
                    help="also write a roofline-basis KernelCostTable JSON "
                         "(run with real TPU + --basis measured via "
                         "repro.kernels.autotune for measured timings)")
    args = ap.parse_args()
    model, measured, sp = run()
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"bench": "kernel", "model_rows": model,
                       "measured_rows": measured, "speedups": sp},
                      f, indent=1)
        print(f"[wrote {args.out}: {len(model) + len(measured)} rows]")
    if args.emit_cost_table:
        table = build_cost_table([1 << 20, 4 << 20, 16 << 20, 64 << 20,
                                  256 << 20])
        table.dump(args.emit_cost_table)
        print(f"[wrote {args.emit_cost_table}: "
              f"{len(table.entries)} entries]")
    if args.check:
        check_against(args.check, model, sp)


if __name__ == "__main__":
    main()
