"""Per-architecture smoke tests (assignment requirement): a REDUCED variant
of each family runs one forward + one train step on CPU; output shapes and
finiteness asserted."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import INPUT_SHAPES
from repro.launch.steps import make_train_step
from repro.models import model as M
from repro.optim import adamw

configs.load_all()

ARCHS = configs.ARCH_IDS


def make_batch(cfg, b=2, s=32, seed=0):
    key = jax.random.PRNGKey(seed)
    shape = (b, s, cfg.num_codebooks) if cfg.num_codebooks else (b, s)
    tok = jax.random.randint(key, shape, 0, cfg.vocab_size)
    batch = {"tokens": tok, "labels": tok}
    if cfg.num_image_tokens:
        batch["image_embeds"] = jax.random.normal(
            key, (b, cfg.num_image_tokens, cfg.d_model), jnp.bfloat16
        )
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_forward_and_train_step(arch):
    cfg = configs.get_config(arch).reduced()
    batch = make_batch(cfg)
    params = M.init(cfg, jax.random.PRNGKey(1))

    logits, _, aux = M.forward(cfg, params, batch["tokens"],
                               image_embeds=batch.get("image_embeds"))
    want = (2, 32, cfg.num_codebooks, cfg.vocab_size) if cfg.num_codebooks \
        else (2, 32, cfg.vocab_size)
    assert logits.shape == want
    assert np.isfinite(np.asarray(logits, np.float32)).all()

    opt = adamw(1e-3)
    step = jax.jit(make_train_step(cfg, opt))
    new_params, opt_state, metrics = step(params, opt.init(params), batch)
    assert np.isfinite(float(metrics["loss"]))
    # params actually changed
    moved = any(
        not np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(new_params))
    )
    assert moved


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_prefill_decode_shapes(arch):
    cfg = configs.get_config(arch).reduced()
    batch = make_batch(cfg)
    params = M.init(cfg, jax.random.PRNGKey(2))
    logits, cache = M.prefill(cfg, params, batch["tokens"],
                              image_embeds=batch.get("image_embeds"))
    tok1 = batch["tokens"][:, :1]
    dl, cache = M.decode_step(cfg, params, cache, tok1)
    want = (2, 1, cfg.num_codebooks, cfg.vocab_size) if cfg.num_codebooks \
        else (2, 1, cfg.vocab_size)
    assert dl.shape == want
    assert np.isfinite(np.asarray(dl, np.float32)).all()
    assert int(cache["t"]) == 33


def test_all_ten_archs_registered_with_exact_specs():
    """The exact assigned architecture numbers are preserved."""
    expect = {
        "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256_000),
        "qwen1.5-4b": (40, 2560, 20, 20, 6912, 151_936),
        "qwen3-0.6b": (28, 1024, 16, 8, 3072, 151_936),
        "llama-3.2-vision-90b": (100, 8192, 64, 8, 28672, 128_256),
        "mamba2-130m": (24, 768, 0, 0, 0, 50_280),
        "musicgen-large": (48, 2048, 32, 32, 8192, 2048),
        "minitron-8b": (32, 4096, 32, 8, 16384, 256_000),
        "llama4-scout-17b-a16e": (48, 5120, 40, 8, 8192, 202_048),
        "qwen2.5-14b": (48, 5120, 40, 8, 13824, 152_064),
        "qwen2-moe-a2.7b": (24, 2048, 16, 16, 1408, 151_936),
    }
    for arch, (nl, d, h, kv, ff, v) in expect.items():
        c = configs.get_config(arch)
        assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads,
                c.d_ff, c.vocab_size) == (nl, d, h, kv, ff, v), arch
        assert c.source


def test_param_counts_in_expected_range():
    """n_params should be near the headline sizes (loose bands)."""
    bands = {
        "qwen3-0.6b": (0.4e9, 1.0e9),
        "minitron-8b": (7e9, 10e9),
        "qwen2.5-14b": (12e9, 17e9),
        "recurrentgemma-9b": (8e9, 11.5e9),
        "llama-3.2-vision-90b": (70e9, 95e9),
        "mamba2-130m": (0.1e9, 0.2e9),
        # Scout is 17B ACTIVE / ~109B TOTAL (16 experts)
        "llama4-scout-17b-a16e": (90e9, 120e9),
    }
    for arch, (lo, hi) in bands.items():
        n = M.n_params(configs.get_config(arch))
        assert lo <= n <= hi, (arch, n)


def test_moe_active_params_below_total():
    for arch in ["llama4-scout-17b-a16e", "qwen2-moe-a2.7b"]:
        cfg = configs.get_config(arch)
        assert M.n_active_params(cfg) < M.n_params(cfg)


def test_block_patterns():
    rg = configs.get_config("recurrentgemma-9b")
    bt = rg.block_types()
    assert len(bt) == 38
    assert bt[:3] == ("rglru", "rglru", "lattn")
    assert bt[-2:] == ("rglru", "rglru")  # remainder stage
    vlm = configs.get_config("llama-3.2-vision-90b")
    assert vlm.block_types().count("xattn") == 20


def test_attention_projections_init_by_their_true_fan_in():
    """q/k/v sum over d_model and the output projection over heads x
    head_dim: with the (in, out) matrix rule they were initialised by head
    count instead, and a 12-layer model diverged in its first SGD step."""
    cfg = configs.get_config("example-100m").reduced(
        d_model=512, num_heads=8, num_kv_heads=2)
    attn = M.init(cfg, jax.random.PRNGKey(0))["stage0"]["b0_attn"]["attn"]
    std = {k: float(jnp.std(v.astype(jnp.float32))) for k, v in attn.items()}
    for name in ("wq", "wk", "wv"):
        np.testing.assert_allclose(std[name], 512 ** -0.5, rtol=0.05)
    np.testing.assert_allclose(std["wo"], (8 * 64) ** -0.5, rtol=0.05)
