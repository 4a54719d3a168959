"""Benchmark driver: one section per paper table/figure.

  python -m benchmarks.run [--full] [--only latency|resources|periodicity|
                                            prediction|kernels]

--full adds the 10000-party rows (slower).
"""
from __future__ import annotations

import sys
import time


def _section(title):
    print(f"\n===== {title} =====", flush=True)


def main() -> None:
    from repro import compile_cache

    compile_cache.enable()
    args = sys.argv[1:]
    only = None
    if "--only" in args:
        only = args[args.index("--only") + 1]
    t0 = time.time()

    if only in (None, "kernels"):
        _section("kernel microbenchmarks (autotuned vs default tiles)")
        from benchmarks import kernel_bench

        # run(), not main(): main()'s argparse would reject our own flags
        kernel_bench.run()

    if only in (None, "periodicity"):
        _section("Fig 3/4: periodicity + linearity (real JAX training)")
        from benchmarks import periodicity

        periodicity.main()

    if only in (None, "prediction"):
        _section("prediction accuracy (central thesis)")
        from benchmarks import prediction_accuracy

        prediction_accuracy.main()

    if only in (None, "drift"):
        _section("§4.2 drift: epoch-time prediction under dataset growth")
        from benchmarks import drift

        drift.main()

    if only in (None, "latency"):
        _section("Fig 7/8: aggregation latency vs parties")
        from benchmarks import latency

        latency.main()

    if only in (None, "resources"):
        _section("Fig 9: container-seconds / cost / savings")
        from benchmarks import resources

        resources.main()

    if only in (None, "jit_ablation"):
        _section("JIT policy ablation (paper timer vs backlog-fill)")
        from benchmarks import jit_ablation

        jit_ablation.main()

    if only in (None, "multijob"):
        _section("multi-job §5.5: deadline priorities vs FIFO under contention")
        from benchmarks import multijob

        multijob.main()

    if only in (None, "fleet"):
        _section("fleet: trace-driven multi-job savings (Fig. 9 headline)")
        from benchmarks import fleet

        print(fleet.HEADER)
        fleet.run(full="--full" in sys.argv)

    if only in (None, "online"):
        _section("online control plane: burst traffic, autoscaling, SLA")
        from benchmarks import online

        print(online.HEADER)
        online.run(full="--full" in sys.argv)

    if only in (None, "hierarchical"):
        _section("hierarchical edge->cloud JIT aggregation (beyond-paper)")
        from benchmarks import hierarchical

        hierarchical.main()

    if only in (None, "dist_agg"):
        _section("distributed aggregation on the 16x16 mesh (t_agg roofline)")
        # subprocess: needs 512 host devices, the rest of the suite needs 1.
        # It is a host-device lowering study, forced onto the CPU: this
        # process may already hold the accelerator.
        import os
        import subprocess

        r = subprocess.run(
            [sys.executable, "-m", "benchmarks.dist_agg"],
            capture_output=True, text=True, timeout=1200,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        print(r.stdout, end="")
        if r.returncode != 0:
            print(f"[dist_agg FAILED]\n{r.stderr[-2000:]}")

    print(f"\n[benchmarks done in {time.time() - t0:.0f}s]")


if __name__ == "__main__":
    main()
