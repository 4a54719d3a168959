"""Lower every model family x step kind x sharding profile for the TPU
platform via AbstractMesh — proves the sharding rules (baseline AND the
§Perf optimized profile: MoE shard_map dispatch, K/V anchoring,
vocab-parallel logits, pure-TP decode params) produce TPU-lowerable
StableHLO without any devices. The full-size compile equivalent is the
512-host-device dry-run (results/dryrun/)."""
import jax
import pytest
from jax.sharding import AbstractMesh

from repro import configs
from repro.configs.base import InputShape
from repro.launch import sharding as shd
from repro.launch import steps as steps_mod
from repro.models.sharding_ctx import activation_sharding

ARCHS = [
    "qwen3-0.6b",            # dense + qk-norm
    "qwen2-moe-a2.7b",       # MoE shared+routed (shard_map dispatch)
    "llama-3.2-vision-90b",  # VLM cross-attn (K/V anchor, vocab-parallel)
    "mamba2-130m",           # SSM (attention-free)
    "recurrentgemma-9b",     # hybrid RG-LRU + local attn
]
SHAPES = {
    "train": InputShape("t", 128, 8, "train"),
    "decode": InputShape("d", 128, 8, "decode"),
}


def _make_mesh():
    return AbstractMesh((2, 2), ("data", "model"))


def _lower(cfg, shape, profile):
    mesh = _make_mesh()
    fn, args, sh, dn = steps_mod.build(cfg, shape, mesh, profile=profile)
    rules = shd.activation_rules(mesh, cfg.sequence_parallel)
    with activation_sharding(mesh, rules, profile=profile):
        traced = jax.jit(fn, in_shardings=sh, donate_argnums=dn).trace(*args)
        return traced.lower(lowering_platforms=("tpu",))


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("kind", ["train", "decode"])
@pytest.mark.parametrize("profile", ["baseline", "optimized"])
def test_tpu_lowering(arch, kind, profile):
    cfg = configs.get_config(arch).reduced()
    lowered = _lower(cfg, SHAPES[kind], profile)
    text = lowered.as_text()
    assert "stablehlo" in text or "func.func" in text
    # sharding annotations survived lowering
    assert "mhlo.sharding" in text or "sdy.sharding" in text


def test_optimized_train_uses_shard_map_moe():
    """The optimized MoE profile must actually take the shard_map path."""
    cfg = configs.get_config("qwen2-moe-a2.7b").reduced()
    base = _lower(cfg, SHAPES["train"], "baseline").as_text()
    opt = _lower(cfg, SHAPES["train"], "optimized").as_text()
    assert ("shard_map" in opt) or ("manual" in opt)
    assert opt != base
