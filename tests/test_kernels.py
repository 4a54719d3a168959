"""Per-kernel validation: Pallas (interpreted on the CPU backend) vs pure-jnp
ref oracles, swept over shapes and dtypes, plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # optional hypothesis (requirements-dev.txt)

from repro.kernels import ref
from repro.kernels.fused_agg import fused_agg
from repro.kernels.ops import interpret_mode
from repro.kernels.pair_fuse import pair_fuse
from repro.kernels.quant_agg import quant_agg, quantize

SHAPES_KN = [(1, 17), (3, 1000), (8, 2048), (5, 3001), (16, 10_000),
             (33, 4096)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("k,n", SHAPES_KN)
@pytest.mark.parametrize("dtype", DTYPES)
def test_fused_agg_matches_ref(k, n, dtype):
    key = jax.random.PRNGKey(k * 1000 + n)
    u = jax.random.normal(key, (k, n), jnp.float32).astype(dtype)
    w = jnp.asarray(np.random.default_rng(0).dirichlet(np.ones(k)),
                    jnp.float32)
    got = fused_agg(u, w, interpret=interpret_mode())
    want = ref.fused_agg_ref(u, w)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


@pytest.mark.parametrize("bn,kb", [(1024, 4), (2048, 8), (4096, 16)])
def test_fused_agg_block_shape_sweep(bn, kb):
    u = jax.random.normal(jax.random.PRNGKey(0), (10, 5000), jnp.float32)
    w = jnp.full((10,), 0.1, jnp.float32)
    got = fused_agg(u, w, bn=bn, kb=kb, interpret=interpret_mode())
    np.testing.assert_allclose(got, ref.fused_agg_ref(u, w), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("n", [1, 100, 8192, 8193, 50_000])
@pytest.mark.parametrize("op", ["mean", "wsum", "max", "min"])
@pytest.mark.parametrize("dtype", DTYPES)
def test_pair_fuse_matches_ref(n, op, dtype):
    ka, kb_ = jax.random.split(jax.random.PRNGKey(n))
    a = jax.random.normal(ka, (n,), jnp.float32).astype(dtype)
    b = jax.random.normal(kb_, (n,), jnp.float32).astype(dtype)
    got = pair_fuse(a, b, op=op, wa=0.3, wb=0.7, interpret=interpret_mode())
    want = ref.pair_fuse_ref(a, b, op, 0.3, 0.7)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


@pytest.mark.parametrize("k,n", [(2, 100), (40, 5000), (64, 4096)])
def test_quant_agg_matches_ref(k, n):
    q = jax.random.randint(jax.random.PRNGKey(1), (k, n), -127, 128,
                           dtype=jnp.int8)
    s = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (k,))) * 0.01
    np.testing.assert_allclose(
        quant_agg(q, s, interpret=interpret_mode()), ref.quant_agg_ref(q, s),
        rtol=1e-5, atol=1e-5
    )


def test_quantize_roundtrip_error_bounded():
    x = jax.random.normal(jax.random.PRNGKey(3), (10_000,)) * 5
    q, s = quantize(x)
    err = np.abs(np.asarray(q, np.float32) * float(s) - np.asarray(x))
    assert err.max() <= float(s) * 0.5 + 1e-6


# ---- tile-override parity (autotuned shapes) --------------------------------
# the autotuner (repro.kernels.autotune) may pick any legal (bn, kb); parity
# vs the oracles must hold for non-aligned n (not a multiple of bn), ragged
# k (not a multiple of kb), and the zero-padding edges (exact multiples)

NON_ALIGNED = [
    # (k, n, bn, kb): n % bn != 0 and k % kb != 0
    (3, 1500, 1024, 8),
    (5, 9000, 4096, 16),
    (13, 40_000, 16384, 8),
]
EXACT_FIT = [
    # zero-length padding edge: both axes exact multiples of the tile
    (8, 2048, 1024, 8),
    (16, 32768, 16384, 16),
]


@pytest.mark.parametrize("k,n,bn,kb", NON_ALIGNED + EXACT_FIT)
@pytest.mark.parametrize("dtype", DTYPES)
def test_fused_agg_tile_override_parity(k, n, bn, kb, dtype):
    u = jax.random.normal(jax.random.PRNGKey(7), (k, n),
                          jnp.float32).astype(dtype)
    w = jnp.asarray(np.random.default_rng(1).dirichlet(np.ones(k)),
                    jnp.float32)
    got = fused_agg(u, w, bn=bn, kb=kb, interpret=interpret_mode())
    want = ref.fused_agg_ref(u, w)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


@pytest.mark.parametrize("k,n,bn,kb", [
    (5, 1500, 1024, 32),   # ragged both axes (kb_align=32 for int8)
    (32, 4096, 2048, 32),  # exact fit, zero padding
    (33, 70_000, 32768, 64),
])
def test_quant_agg_tile_override_parity(k, n, bn, kb):
    q = jax.random.randint(jax.random.PRNGKey(4), (k, n), -127, 128,
                           dtype=jnp.int8)
    s = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (k,))) * 0.01
    np.testing.assert_allclose(
        quant_agg(q, s, bn=bn, kb=kb, interpret=interpret_mode()),
        ref.quant_agg_ref(q, s),
        rtol=1e-5, atol=1e-5,
    )


@pytest.mark.parametrize("n,bn", [
    (1, 1024),        # all padding but one element
    (5000, 2048),     # non-aligned
    (8192, 8192),     # exact fit, zero padding, single grid step
    (100_000, 32768),
])
@pytest.mark.parametrize("op", ["mean", "wsum"])
def test_pair_fuse_bn_override_parity(n, bn, op):
    ka, kb_ = jax.random.split(jax.random.PRNGKey(n))
    a = jax.random.normal(ka, (n,), jnp.float32)
    b = jax.random.normal(kb_, (n,), jnp.float32)
    np.testing.assert_allclose(
        pair_fuse(a, b, op=op, wa=0.3, wb=0.7, bn=bn,
                  interpret=interpret_mode()),
        ref.pair_fuse_ref(a, b, op, 0.3, 0.7),
        rtol=1e-5, atol=1e-5,
    )


def test_quantize_roundtrip_zero_and_tiny_inputs():
    """Degenerate scales: all-zero input keeps scale 1 (no div-by-zero) and
    round-trips exactly; a single-element update round-trips within s/2."""
    q, s = quantize(jnp.zeros((257,)))
    assert float(s) == 1.0
    assert not np.asarray(q, np.float32).any()
    x = jnp.asarray([3.7], jnp.float32)
    q1, s1 = quantize(x)
    assert abs(float(q1[0]) * float(s1) - 3.7) <= float(s1) * 0.5 + 1e-6


# ---- properties ------------------------------------------------------------
@given(
    k=st.integers(1, 12),
    n=st.integers(1, 500),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=20, deadline=None)
def test_fused_agg_weighted_mean_bounds(k, n, seed):
    """A convex combination never exceeds the per-coordinate min/max."""
    u = jax.random.normal(jax.random.PRNGKey(seed), (k, n), jnp.float32)
    w = jnp.full((k,), 1.0 / k, jnp.float32)
    got = np.asarray(fused_agg(u, w, interpret=interpret_mode()))
    lo = np.asarray(jnp.min(u, axis=0))
    hi = np.asarray(jnp.max(u, axis=0))
    assert (got >= lo - 1e-5).all() and (got <= hi + 1e-5).all()


@given(n=st.integers(1, 300), seed=st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_pair_fuse_commutative_ops(n, seed):
    ka, kb_ = jax.random.split(jax.random.PRNGKey(seed))
    a = jax.random.normal(ka, (n,), jnp.float32)
    b = jax.random.normal(kb_, (n,), jnp.float32)
    for op in ["mean", "max", "min"]:
        np.testing.assert_allclose(
            pair_fuse(a, b, op=op, interpret=interpret_mode()),
            pair_fuse(b, a, op=op, interpret=interpret_mode()), rtol=1e-6
        )


# ---- TPU semantics on the CPU ------------------------------------------------
# The TPU interpreter models what the generic one does not: memory outside
# the arrays reads as NaN, and an output block is never read back from HBM,
# so a block revisited after another one would lose its partial sum. Ragged
# last blocks on both axes and several K blocks per output block.
@pytest.mark.parametrize("kernel,k,n,bn,kb", [
    ("fused_agg", 13, 40_000, 16384, 8),
    ("fused_agg", 32, 9216, 8192, 8),
    ("quant_agg", 70, 5000, 4096, 32),
    ("pair_fuse", 2, 9216, 8192, 2),
])
def test_kernels_under_tpu_interpreter(kernel, k, n, bn, kb):
    from jax.experimental.pallas import tpu as pltpu

    tpu = pltpu.InterpretParams()
    key = jax.random.PRNGKey(k)
    if kernel == "pair_fuse":
        a, b = jax.random.normal(key, (2, n), jnp.float32)
        got = pair_fuse(a, b, op="wsum", wa=0.3, wb=0.7, bn=bn, interpret=tpu)
        want = ref.pair_fuse_ref(a, b, "wsum", 0.3, 0.7)
    elif kernel == "fused_agg":
        u = jax.random.normal(key, (k, n), jnp.float32)
        w = jnp.linspace(0.1, 1.0, k, dtype=jnp.float32)
        got = fused_agg(u, w, bn=bn, kb=kb, interpret=tpu)
        want = ref.fused_agg_ref(u, w)
    else:
        q = jax.random.randint(key, (k, n), -127, 128, dtype=jnp.int8)
        s = jnp.linspace(0.001, 0.01, k, dtype=jnp.float32)
        got = quant_agg(q, s, bn=bn, kb=kb, interpret=tpu)
        want = ref.quant_agg_ref(q, s)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
