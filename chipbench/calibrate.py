#!/usr/bin/env python3
"""Readings that set the benchmark's limits and rates, on the chip.

    python3 chipbench/calibrate.py limits --workload vgg16.backlog \\
        --seconds 5 --seeds 1,2,3 --control-seeds 101,102,103
    python3 chipbench/calibrate.py knee --workload vgg16.stream \\
        --seconds 10 --seed 5 --rates 80,90,100

``limits`` runs the cell's window once per seed, in one process: the
program on ``--seeds`` (the lower reading of each checked number is the
largest over them) and the control, the plain reference in bfloat16 in the
aggregator's place, on ``--control-seeds`` (the upper reading is the
smallest). ``knee`` serves the cell's open-loop mix at each rate and
reports whether the backlog grew: the latency of the last fifth of the
rounds against the first fifth, and the generator's lateness. Neither is
run by the benchmark's own runs. Each reading is one JSON line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

import numpy as np  # noqa: E402

import run  # noqa: E402


def _ints(s: str):
    return [int(x) for x in s.split(",") if x]


def limits(cell, args) -> None:
    import jax.numpy as jnp

    from chipbench import control

    runs = [("program", s, None) for s in _ints(args.seeds)]
    runs += [("control", s, control.executor_of(cell.config, jnp.bfloat16))
             for s in _ints(args.control_seeds)]
    for kind, seed, executor_of in runs:
        res = run.measure(cell, seed, args.seconds, executor_of=executor_of)
        print(json.dumps({
            "kind": kind, "seed": seed, "rounds": len(res["win"].rounds),
            "model_gap": max(res["gaps"].values(), default=float("inf")),
            "gaps": res["gaps"], "bad_rounds": len(res["bad"])}),
            flush=True)
        del res


def knee(cell, args) -> None:
    for rate in [float(x) for x in args.rates.split(",")]:
        mix = dict(cell.mix, rate_updates_per_s=rate)
        res = run.measure(dataclasses.replace(cell, mix=mix), args.seed,
                          args.seconds)
        win = res["win"]
        lat = np.array([r.ready - r.due for r in win.rounds]) * 1e3
        fifth = max(1, len(lat) // 5)
        late = win.lateness * 1e3
        print(json.dumps({
            "rate": rate, "rounds": len(lat),
            "p50_ms": float(np.percentile(lat, 50)),
            "p90_ms": float(np.percentile(lat, 90)),
            "p95_ms": float(np.percentile(lat, 95)),
            "first_fifth_ms": float(np.median(lat[:fifth])),
            "last_fifth_ms": float(np.median(lat[-fifth:])),
            "late_p99_ms": float(np.percentile(late, 99)),
            "late_max_ms": float(late.max()),
            "drain_after_close_s": win.rounds[-1].ready - win.t_close,
            "model_gap": max(res["gaps"].values(), default=float("inf"))}),
            flush=True)
        del res, win


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    sub = ap.add_subparsers(dest="cmd", required=True)
    lim = sub.add_parser("limits")
    lim.add_argument("--seeds", required=True)
    lim.add_argument("--control-seeds", default="")
    kn = sub.add_parser("knee")
    kn.add_argument("--seed", type=int, required=True)
    kn.add_argument("--rates", required=True)
    for p in (lim, kn):
        p.add_argument("--workload", required=True)
        p.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax

    from chipbench import plan

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    run.log(f"compile cache: {run.enable_compile_cache()}")
    cell = plan.load_cell(args.workload)
    (limits if args.cmd == "limits" else knee)(cell, args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
