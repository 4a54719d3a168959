#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 chipbench/run.py --workload vgg16.backlog --seed 7 \\
        --seconds 30 --trace 0

From the checkout's root, on a machine with the chips the cell asks for.
Set-up makes the cell's K party updates and global model on the device from
the seed, and serves one untimed round to compile every program; then the
window serves rounds through the aggregator (``loop.py``) for ``--seconds``.
Once it has closed and the device's peak memory is read, a seeded sample of
the rounds and the last one are compared with the plain reference
(``reference.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted`` and ``failed`` (rounds), ``metrics`` (the cell's end-to-end
metrics; with ``--trace 1`` its per-layer ones instead, read from a
profiler trace of the window's first seconds), ``device``, with ``--trace
1`` ``breakdown``, and last ``checks``: each number compared, beside its
limit. The last lines of standard error repeat the checks. Without a TPU,
or with fewer chips than the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]

import numpy as np  # noqa: E402

#: profiler-on seconds at the start of a ``--trace 1`` window
TRACE_SECONDS = 3.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` if
    set, else the fixed ``.jax_cache/`` in the checkout. Every program is
    kept, however quickly it compiled, so a second run compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def check_round(config: dict, updates: list, s, lr: float) -> float:
    """The widest gap between one published model and the reference's."""
    from chipbench import reference

    return reference.round_gap([updates[i] for i in s.order], s.n_examples,
                               s.prev, lr, config["algorithm"], s.published)


def measure(cell, seed: int, seconds: float, *, trace: bool = False,
            executor_of=None, compile_log=None, t_start: float = None,
            device_kind: str = None) -> dict:
    """Set up, serve the window, check it; everything a result line needs."""
    import jax

    from chipbench import loop, roofline, traffic
    from chipbench import trace as trace_mod

    t_start = time.perf_counter() if t_start is None else t_start
    config, k = cell.config, int(cell.mix["parties_per_round"])
    lr = float(config.get("server_lr", 1.0))
    tr = traffic.make(cell.mix, seed, seconds)
    updates, global0 = loop.make_inputs(config, k, seed)
    loop.warm_up(config, tr, updates, global0, executor_of)
    setup_s = time.perf_counter() - t_start

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        t_win = time.perf_counter()
        win = loop.run_window(config, tr, updates, global0, seconds,
                              executor_of=executor_of, trace_dir=trace_dir,
                              trace_seconds=TRACE_SECONDS)
        compiles = (compile_log.count(t_win, time.perf_counter())
                    if compile_log is not None else None)
        peaks_in_use = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                        for d in jax.local_devices()[:cell.chips]]
        peak = max((p for p in peaks_in_use if p is not None), default=None)
        traced = None
        if trace_dir is not None:
            pk = roofline.peaks(device_kind) if device_kind else None
            traced = trace_mod.window(
                trace_mod.load(trace_mod.find_xplane(Path(trace_dir))),
                roofline.least_bytes_per_round(config, k), k,
                pk["hbm_bytes_per_s"] if pk else float("nan"))
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    rounds = win.rounds
    bad = [r.round_idx for r in rounds
           if r.drained != k or r.published != 1]
    gaps = {s.round_idx: check_round(config, updates, s, lr)
            for s in win.sampler.rounds()}
    return dict(setup_s=setup_s, win=win, compiles=compiles,
                peak=peak, traced=traced, bad=bad, gaps=gaps, k=k,
                attempted=len(rounds) if tr.closed else tr.n_rounds)


def end_to_end(res: dict) -> dict:
    """Every end-to-end reading the window gives, by metric name."""
    win, k = res["win"], res["k"]
    rounds = win.rounds
    out = {"setup_s": res["setup_s"]}
    if rounds:
        out["updates_per_s"] = k * len(rounds) / (rounds[-1].ready - win.t0)
        lat = np.array([r.ready - r.due for r in rounds]) * 1e3
        out["round_latency_p50_ms"] = float(np.percentile(lat, 50))
    return out


def report(cell, res: dict, trace: bool, limits: dict) -> dict:
    """The result line, and the earlier lines on standard error."""
    import jax

    win, k = res["win"], res["k"]
    rounds = win.rounds
    lat = np.array([r.ready - r.due for r in rounds]) * 1e3
    e2e = end_to_end(res)
    log(f"set-up {res['setup_s']:.3f} s; window {len(rounds)} rounds of "
        f"{k} updates, {rounds[-1].ready - win.t0 if rounds else 0:.3f} s; "
        f"compiles in the window: {res['compiles']}")
    if rounds:
        log(f"updates/s {e2e['updates_per_s']}; round latency ms: p50 "
            f"{np.percentile(lat, 50)}, p90 {np.percentile(lat, 90)}, p95 "
            f"{np.percentile(lat, 95)}, max {lat.max()} ({len(lat)} rounds)")
    if len(win.lateness) and not cell.mix["arrivals"] == "closed":
        late = win.lateness * 1e3
        log(f"generator lateness ms: p50 {np.percentile(late, 50)}, p99 "
            f"{np.percentile(late, 99)}, max {late.max()} "
            f"({len(late)} updates)")
    log(f"peak_bytes_in_use {res['peak']}")

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": res["peak"]}
    metrics = {}
    if trace:
        w = res["traced"]
        if w is not None:
            device["busy_s"] = w.busy_s()
            device["window_s"] = w.window_s
            for m in cell.per_layer:
                v = m.read(w)
                if v is not None:
                    metrics[m.name] = {"value": v, "unit": m.unit}
            log(f"traced {w.rounds} rounds, {w.window_s:.6f} s, device busy "
                f"{w.busy_s():.6f} s, {w.launches()} programs launched")
    else:
        for m in cell.end_to_end:
            if m.name in e2e:
                metrics[m.name] = {"value": e2e[m.name], "unit": m.unit}

    gap = max(res["gaps"].values(), default=float("inf"))
    checks = {
        "model_gap": {"value": gap, "limit": limits["model_gap"]},
        "bad_rounds": {"value": len(res["bad"]), "limit": 0},
    }
    failed = len(res["bad"]) + sum(g > limits["model_gap"]
                                   for g in res["gaps"].values())
    failed += res["attempted"] - len(rounds)
    correct = bool(rounds) and all(c["value"] <= c["limit"]
                                   for c in checks.values())
    out = {"correct": correct, "attempted": res["attempted"],
           "failed": failed, "metrics": metrics, "device": device}
    if trace and res["traced"] is not None:
        out["breakdown"] = res["traced"].breakdown()
    out["checks"] = checks
    log(f"checked rounds {sorted(res['gaps'])}: gaps "
        f"{[res['gaps'][r] for r in sorted(res['gaps'])]}")
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    from chipbench import loop, plan, roofline

    devs = jax.devices()
    cell = plan.load_cell(args.workload)
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        log(f"chipbench: {args.workload} needs {cell.chips} TPU chip(s); "
            f"JAX found {len(devs)} {devs[0].platform} device(s)")
        return 2
    roofline.peaks(devs[0].device_kind)  # an unknown chip is an error
    log(f"compile cache: {enable_compile_cache()}")
    compile_log = loop.CompileLog()
    res = measure(cell, args.seed, args.seconds, trace=bool(args.trace),
                  compile_log=compile_log, t_start=T_START,
                  device_kind=devs[0].device_kind)
    out = report(cell, res, bool(args.trace), cell.config["limits"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
