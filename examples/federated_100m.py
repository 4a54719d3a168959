"""End-to-end driver: federated training of the ~100M-parameter
`example-100m` config (12L, d=768, vocab 8k) across 4 parties for a few
hundred local steps total, with JIT-scheduled aggregation.

This is the (b) end-to-end deliverable: real model, real data pipeline,
real optimizer, real fusion kernels, real prediction/scheduling — CPU-sized
rounds (expect ~20-40 min on one core; use --rounds/--sequences to shrink).

  PYTHONPATH=src python examples/federated_100m.py [--rounds N] [--sequences N]

The scheduling timeline is priced by replaying the measured arrivals
through the strategy registry: pass --policy to cost the same kind of run
under eager_ao / eager_serverless / batched / lazy instead of the default
deterministic JIT timeline (see also benchmarks/real_ablation.py, which
prices ALL strategies from one shared run).
"""
import argparse

from repro import compile_cache, configs
from repro.api import Platform
from repro.core import STRATEGIES
from repro.core.jobspec import FLJobSpec, PartySpec
from repro.models import model as M

configs.load_all()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--sequences", type=int, default=192)
    ap.add_argument("--parties", type=int, default=4)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--policy", choices=list(STRATEGIES), default=None,
                    help="deployment strategy to price the run under "
                         "(default: the deterministic JIT timeline)")
    args = ap.parse_args()

    cfg = configs.get_config("example-100m")
    n_params = M.n_params(cfg)
    print(f"example-100m: {n_params/1e6:.1f}M params, "
          f"{args.parties} parties, {args.rounds} rounds")
    # steps/round/party = sequences/parties/batch; total local steps:
    steps = args.rounds * args.sequences // args.batch_size
    print(f"~{steps} total local train steps")

    spec = FLJobSpec(
        job_id="federated-100m",
        model_arch=cfg.name,
        model_bytes=n_params * 4,
        aggregation_algorithm="fedprox",
        prox_mu=0.001,
        rounds=args.rounds,
        lr=0.05,
        batch_size=args.batch_size,
        parties={f"p{i}": PartySpec(f"p{i}") for i in range(args.parties)},
    )
    result = Platform().train(
        cfg, spec, policy=args.policy, n_sequences=args.sequences,
        heterogeneous=True, eval_sequences=32, seed=0, verbose=True,
    )
    records = result.records
    print("\nfinal eval loss:", records[-1].global_loss)
    print(f"{result.metrics.strategy} container-seconds: "
          f"{result.metrics.container_seconds:.1f} "
          f"(${result.metrics.cost_usd:.4f})")
    pred_errs = [
        abs(r.t_rnd_pred - max(r.arrivals.values())) / max(r.arrivals.values())
        for r in records[1:]
    ]
    if pred_errs:  # needs >= 2 rounds (round 1 has no observations yet)
        print(f"mean t_rnd prediction error (rounds 2+): "
              f"{100*sum(pred_errs)/len(pred_errs):.1f}%")


if __name__ == "__main__":
    compile_cache.enable()
    main()
