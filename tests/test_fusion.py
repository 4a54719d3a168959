"""Fusion algorithms: closed-form equivalence, and the LINEARITY properties
JIT aggregation exploits — incremental == batch, order-independence,
partial-merge (parallel aggregation) == sequential."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # optional hypothesis (requirements-dev.txt)

from repro.fl.fusion import FedAvg, FedProx, FedSGD, FusionState, get_algorithm


def _updates(k=4, seed=0, shapes=((8, 4), (16,), (2, 3, 5))):
    keys = jax.random.split(jax.random.PRNGKey(seed), k * len(shapes))
    out = []
    for i in range(k):
        out.append({
            f"w{j}": jax.random.normal(keys[i * len(shapes) + j], s)
            for j, s in enumerate(shapes)
        })
    return out


def _closed_form(updates, weights):
    total = sum(weights)
    return jax.tree.map(
        lambda *xs: sum(w * x for w, x in zip(weights, xs)) / total, *updates
    )


def test_fedavg_weighted_mean_closed_form():
    ups = _updates(4)
    n_ex = [10, 20, 30, 40]
    alg = FedAvg()
    fused = alg.fuse(ups, n_ex)
    want = _closed_form(ups, [float(n) for n in n_ex])
    for k in fused:
        np.testing.assert_allclose(fused[k], want[k], rtol=2e-5, atol=2e-5)


def test_fedsgd_applies_gradient_step():
    model = {"w": jnp.ones((4, 4))}
    grads = [{"w": jnp.full((4, 4), 2.0)}, {"w": jnp.full((4, 4), 4.0)}]
    alg = FedSGD()
    fused = alg.fuse(grads, [1, 1])
    new = alg.apply(model, fused, lr=0.1)
    np.testing.assert_allclose(new["w"], 1.0 - 0.1 * 3.0, rtol=1e-6)


def test_fedprox_server_side_equals_fedavg():
    ups = _updates(3)
    n_ex = [5, 5, 10]
    a = FedAvg().fuse(ups, n_ex)
    b = FedProx().fuse(ups, n_ex)
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=1e-6)


# ---- linearity properties (§2.1 / §4.2) -------------------------------------
@given(k=st.integers(2, 8), seed=st.integers(0, 10_000))
@settings(max_examples=15, deadline=None)
def test_incremental_equals_batch(k, seed):
    ups = _updates(k, seed=seed, shapes=((6, 7),))
    ws = list(np.random.default_rng(seed).uniform(1, 100, k))
    st_ = FusionState()
    for u, w in zip(ups, ws):
        st_ = st_.fold(u, w)
    inc = st_.result()
    want = _closed_form(ups, ws)
    np.testing.assert_allclose(inc["w0"], want["w0"], rtol=2e-4, atol=2e-4)


@given(seed=st.integers(0, 10_000))
@settings(max_examples=15, deadline=None)
def test_fusion_order_independent(seed):
    ups = _updates(5, seed=seed, shapes=((11,),))
    ws = [1.0, 2.0, 3.0, 4.0, 5.0]
    rng = np.random.default_rng(seed)
    perm = rng.permutation(5)
    a = FusionState()
    for u, w in zip(ups, ws):
        a = a.fold(u, w)
    b = FusionState()
    for i in perm:
        b = b.fold(ups[i], ws[i])
    np.testing.assert_allclose(a.result()["w0"], b.result()["w0"],
                               rtol=2e-4, atol=2e-4)


@given(k=st.integers(3, 9), n_shards=st.integers(2, 4),
       seed=st.integers(0, 10_000))
@settings(max_examples=15, deadline=None)
def test_parallel_partials_merge_equals_sequential(k, n_shards, seed):
    """Parallel aggregation (§5.4): shard updates across workers, merge the
    partial FusionStates — identical to one sequential pass."""
    ups = _updates(k, seed=seed, shapes=((9,),))
    ws = list(np.random.default_rng(seed).uniform(1, 10, k))
    seq = FusionState()
    for u, w in zip(ups, ws):
        seq = seq.fold(u, w)
    partials = []
    for s in range(n_shards):
        p = FusionState()
        for u, w in list(zip(ups, ws))[s::n_shards]:
            p = p.fold(u, w)
        partials.append(p)
    merged = partials[0]
    for p in partials[1:]:
        merged = merged.merge(p)
    np.testing.assert_allclose(merged.result()["w0"], seq.result()["w0"],
                               rtol=2e-4, atol=2e-4)
    assert merged.n_fused == seq.n_fused == k


def test_checkpoint_resume_roundtrip():
    """Preemption (§5.5): a checkpointed partial aggregate resumes to the
    same final result."""
    ups = _updates(6, shapes=((5, 5),))
    ws = [1.0] * 6
    direct = FusionState()
    for u, w in zip(ups, ws):
        direct = direct.fold(u, w)
    # interrupt after 3, "checkpoint" (it's a value), resume
    part = FusionState()
    for u, w in list(zip(ups, ws))[:3]:
        part = part.fold(u, w)
    snap = {"acc": part.acc, "total_weight": part.total_weight,
            "n_fused": part.n_fused}
    resumed = FusionState(**snap)
    for u, w in list(zip(ups, ws))[3:]:
        resumed = resumed.fold(u, w)
    np.testing.assert_allclose(resumed.result()["w0"], direct.result()["w0"],
                               rtol=1e-5)


# ---- the slab accumulator: one program per fold and per finish ---------------
def _mixed_tree(seed, dtype):
    """A scalar, 1-D, 2-D and 4-D leaf in nested containers, 13,208
    elements: every leaf under ``PACK_BELOW``, so the accumulator is one
    packed slab and the finish slices each leaf out of it."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {
        "scale": jax.random.normal(ks[0], ()).astype(dtype),
        "layers": [jax.random.normal(ks[1], (37,)).astype(dtype),
                   jax.random.normal(ks[2], (9, 130)).astype(dtype)],
        "conv": jax.random.normal(ks[3], (3, 2, 40, 50)).astype(dtype),
    }


#: one fold step of one leaf, compiled as the fold compiles it: a backend
#: that contracts ``a + w*u`` into a fused multiply-add (XLA's CPU backend
#: does) then contracts it on both sides
_fold_step = jax.jit(lambda a, u, w: a + w * u.astype(jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flat_fold_matches_per_leaf_arithmetic(dtype):
    """Each element sees the per-leaf operations in the same order — ``w*u``
    first, then ``a + w*u``, then ``/ tw`` — so the slab fold equals them
    exactly, and the mean comes back in the update's tree."""
    ups = [_mixed_tree(s, dtype) for s in range(4)]
    ws = [3.0, 0.7, 12.0, 1.5]
    want = jax.tree.map(lambda u: u.astype(jnp.float32) * ws[0], ups[0])
    for u, w in zip(ups[1:], ws[1:]):
        want = jax.tree.map(lambda a, x, w=w: _fold_step(a, x, w), want, u)
    tw = sum(ws)
    want = jax.tree.map(lambda a: a / tw, want)

    st_ = FusionState()
    for u, w in zip(ups, ws):
        st_ = st_.fold(u, w)
    got = st_.result()
    assert jax.tree.structure(got) == jax.tree.structure(ups[0])
    for g, x, u in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                       jax.tree.leaves(ups[0])):
        assert g.shape == u.shape and g.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(g), np.asarray(x))

    # the published model keeps the global model's tree, shapes and dtypes
    model = _mixed_tree(99, dtype)
    for alg in (FedAvg(), FedSGD()):
        new = st_.finish(alg, model, 0.5)
        assert jax.tree.structure(new) == jax.tree.structure(model)
        for n, m, e in zip(jax.tree.leaves(new), jax.tree.leaves(model),
                           jax.tree.leaves(alg.apply(model, want, 0.5))):
            assert n.shape == m.shape and n.dtype == m.dtype
            np.testing.assert_array_equal(np.asarray(n, np.float32),
                                          np.asarray(e, np.float32))


def _backend_compiles(fn) -> int:
    events = []

    def on(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            events.append(event)

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        fn()
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    return len(events)


def test_folding_the_same_shapes_again_compiles_nothing():
    """The compile key is the tree and its leaf shapes; the weights, the
    total weight and the server step are traced."""
    model = _mixed_tree(7, jnp.float32)

    def round_(seed, ws, lr):
        st_ = FusionState()
        for i, w in enumerate(ws):
            st_ = st_.fold(_mixed_tree(seed + i, jnp.float32), w)
        jax.block_until_ready((st_.result(), st_.finish(FedSGD(), model, lr)))

    round_(0, [1.0, 2.0, 3.0], 1.0)
    assert _backend_compiles(lambda: round_(10, [5.0, 0.25, 7.5, 2.0],
                                            0.3)) == 0


def _count_primitive(jaxpr, name) -> int:
    """Equations named ``name`` in ``jaxpr`` and the jaxprs nested in it."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            n += 1
            continue
        for v in eqn.params.values():
            for j in (v if isinstance(v, (list, tuple)) else [v]):
                j = getattr(j, "jaxpr", j)  # a ClosedJaxpr's Jaxpr
                if hasattr(j, "eqns"):
                    n += _count_primitive(j, name)
    return n


def test_fold_is_one_program_with_one_kernel():
    """A fold of the whole tree is one compiled program, with no Pallas
    kernel in it, whose every output is written into a donated buffer of
    the accumulator; a finish is one program too."""
    from repro.kernels import SlabAcc, accumulate
    from repro.kernels.ops import fold_into

    def tree(seed, dtype):  # one leaf a slab of its own, four packed
        wide = jax.random.normal(jax.random.PRNGKey(seed), (256, 300))
        return {**_mixed_tree(seed, dtype), "wide": wide.astype(dtype)}

    st_ = FusionState().fold(tree(0, jnp.float32), 2.0)
    acc, update = st_.acc, tree(1, jnp.bfloat16)
    as_acc = lambda slabs: SlabAcc(slabs, acc.treedef, acc.plan)  # noqa: E731
    fold = jax.make_jaxpr(
        lambda slabs, u: accumulate(as_acc(slabs), u, 0.5).slabs)(
            acc.slabs, update)
    (program,) = fold.jaxpr.eqns
    assert program.params["name"] == "fold_into"
    assert _count_primitive(fold.jaxpr, "pallas_call") == 0
    n_out = len(program.outvars)
    assert n_out == acc.plan.n_slabs == 2
    assert program.params["donated_invars"] == (
        (True,) * n_out + (False,) * (len(program.invars) - n_out))
    lowered = fold_into.lower(acc, jax.tree.leaves(update), np.float32(0.5))
    assert lowered.as_text().count("tf.aliasing_output") == n_out

    model = tree(2, jnp.float32)
    finish = jax.make_jaxpr(
        lambda slabs: FusionState(as_acc(slabs), 2.0).finish(FedSGD(), model)
    )(acc.slabs)
    assert len(finish.jaxpr.eqns) == 1
    assert _count_primitive(finish.jaxpr, "pallas_call") == 0


def test_fold_and_merge_spend_the_state_they_consume():
    """fold and merge donate the accumulator to the state they return; the
    consumed state raises when used, and merge never spends ``other``."""
    ups = _updates(3, shapes=((4, 3),))
    a = FusionState().fold(ups[0], 1.0)
    b = a.fold(ups[1], 2.0)
    for use in (lambda: a.result(), lambda: a.fold(ups[2], 1.0),
                lambda: a.merge(b), lambda: b.merge(a),
                lambda: a.finish(FedAvg(), ups[0])):
        with pytest.raises(RuntimeError, match="folded or merged"):
            use()
    other = FusionState().fold(ups[2], 4.0)
    merged = b.merge(other)
    with pytest.raises(RuntimeError):
        b.result()
    np.testing.assert_array_equal(other.result()["w0"], ups[2]["w0"])
    np.testing.assert_allclose(
        merged.result()["w0"],
        (ups[0]["w0"] + 2 * ups[1]["w0"] + 4 * ups[2]["w0"]) / 7,
        rtol=1e-6, atol=1e-6)
