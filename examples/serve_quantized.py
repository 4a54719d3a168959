"""Beyond-paper example: int8-compressed model updates. Parties quantise
updates before upload (4x smaller t_comm — which JIT's t_upd prediction
picks up automatically), and the aggregator fuses them with the
dequantise-accumulate Pallas kernel without materialising fp32 updates.

  PYTHONPATH=src python examples/serve_quantized.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache, configs
from repro.core.jobspec import FLJobSpec, PartySpec
from repro.core.prediction import UpdatePredictor
from repro.kernels import fuse_quantized, fuse_updates, quantize_update
from repro.models import model as M

configs.load_all()


def main():
    cfg = configs.get_config("qwen3-0.6b").reduced(
        num_layers=2, d_model=128, vocab_size=256
    )
    key = jax.random.PRNGKey(0)
    updates = [
        jax.tree.map(
            lambda p, k=k: p + 0.01 * jax.random.normal(
                jax.random.PRNGKey(k), p.shape, jnp.float32
            ).astype(p.dtype),
            M.init(cfg, key),
        )
        for k in range(4)
    ]
    weights = [0.1, 0.2, 0.3, 0.4]

    exact = fuse_updates(updates, weights)
    qs, ss = zip(*(quantize_update(u) for u in updates))
    fused_q = fuse_quantized(list(qs), list(ss), weights)

    errs = [
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b)))
        for a, b in zip(jax.tree.leaves(exact), jax.tree.leaves(fused_q))
    ]
    # per-leaf error bound: int8 rounding is <= 0.5 quant-step per update
    # and the bf16 inputs carry another ~0.5 step themselves (max_abs =
    # 127*scale and bf16 eps = 2^-8, so 127*scale/256 ~ scale/2); fusion is
    # a convex combination -> bound = 1.0 * sum_k w_k * scale_k
    bounds = [
        sum(w * float(jnp.max(s_leaf))
            for w, s_leaf in zip(weights, leaves))
        for leaves in zip(*(jax.tree.leaves(s) for s in ss))
    ]
    print(f"max abs fusion error from int8 updates: {max(errs):.5f} "
          f"(bound {max(bounds):.5f})")

    # comm-time effect on JIT's schedule
    n_bytes = M.n_params(cfg) * 4
    spec = FLJobSpec(
        job_id="q", model_arch=cfg.name, model_bytes=n_bytes,
        parties={"p0": PartySpec("p0", epoch_time_s=60.0, bw_up=5e6,
                                 bw_down=5e6)},
    )
    pred_fp32 = UpdatePredictor(spec)
    t_fp32 = pred_fp32.t_upd("p0")
    spec.model_bytes = n_bytes // 4  # int8 + scales
    pred_int8 = UpdatePredictor(spec)
    t_int8 = pred_int8.t_upd("p0")
    print(f"t_upd fp32={t_fp32:.2f}s -> int8={t_int8:.2f}s "
          f"(JIT defers {t_fp32 - t_int8:.2f}s longer)")
    for e, b in zip(errs, bounds):
        assert e <= b * 1.05 + 1e-7, (e, b)


if __name__ == "__main__":
    compile_cache.enable()
    main()
