"""Training launcher: build the mesh, shard params/optimizer per the
launch-layer rules, and run real train steps on synthetic data.

  # CPU smoke (reduced config, 1x1 mesh, real execution):
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --reduced --steps 5

  # production mesh on real hardware (or --force-host for a CPU dry run):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-14b --shape train_4k

The full-size path is exercised without allocation by launch/dryrun.py;
this driver actually initialises and steps.
"""
import argparse
import os
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config on a 1x1 mesh (CPU)")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--profile", default="baseline",
                    choices=["baseline", "optimized"])
    ap.add_argument("--force-host", action="store_true",
                    help="force 512 host devices for the production mesh")
    args = ap.parse_args(argv)

    if args.force_host:
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.configs.base import INPUT_SHAPES, InputShape
    from repro.launch import sharding as shd
    from repro.launch import steps as steps_mod
    from repro.launch.mesh import make_host_mesh, make_production_mesh
    from repro.models import model as M
    from repro.models.sharding_ctx import activation_sharding
    from repro.models.spec import init_params
    from repro.optim import adamw

    cfg = configs.get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
        mesh = make_host_mesh(1, 1)
        shape = InputShape("smoke", args.seq_len, args.batch, "train")
    else:
        mesh = make_production_mesh()
        shape = INPUT_SHAPES[args.shape]

    fn, abstract_args, in_shardings, donate = steps_mod.build(
        cfg, shape, mesh, profile=args.profile)
    rules = shd.activation_rules(mesh, cfg.sequence_parallel)

    key = jax.random.PRNGKey(0)
    params = init_params(key, M.param_specs(cfg))
    opt = adamw(3e-4)
    opt_state = opt.init(params)
    rng = jax.random.PRNGKey(1)

    with activation_sharding(mesh, rules, profile=args.profile):
        step = jax.jit(fn, in_shardings=in_shardings, donate_argnums=donate)
        for i in range(args.steps):
            rng, k = jax.random.split(rng)
            tok_shape = ((shape.global_batch, shape.seq_len,
                          cfg.num_codebooks) if cfg.num_codebooks
                         else (shape.global_batch, shape.seq_len))
            batch = {
                "tokens": jax.random.randint(k, tok_shape, 0,
                                             cfg.vocab_size, jnp.int32),
            }
            batch["labels"] = jnp.roll(batch["tokens"], -1, axis=1)
            if cfg.num_image_tokens:
                batch["image_embeds"] = jnp.zeros(
                    (shape.global_batch, cfg.num_image_tokens, cfg.d_model),
                    jnp.dtype(cfg.dtype))
            t0 = time.time()
            params, opt_state, metrics = step(params, opt_state, batch)
            loss = float(metrics["loss"])
            print(f"step {i}: loss={loss:.4f} ({time.time()-t0:.2f}s)",
                  flush=True)
            assert loss == loss, "NaN loss"
    print("ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
