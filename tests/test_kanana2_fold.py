"""bf16 MoE updates through the aggregator's served path, on the CPU.

The layout is a small Kanana-2 (DeepSeek-V3): hidden 64, MLA with 4 heads
and a kv_lora rank of 16, a dense layer 0, then 2 MoE layers of 8 routed
experts of width 24 (stacked ``(e, in, out)``), a router with its
correction bias and 2 shared experts, a vocabulary of 256; every update is
seeded bf16. A round (``MessageQueue`` -> ``AggregationExecutor.drain`` ->
``finish_round``, FedAvg) must equal a plain ``jax.numpy`` reference that
sums in fp32 in publish order and rounds to bf16 once, bit for bit; a chip's
share of the layers must fuse to its share of the uncut result; the fold
must stage a bf16 update as bf16.
"""
import glob
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.queue import MessageQueue
from repro.fl.aggregator import AggregationExecutor
from repro.kernels.ops import (PACK_BELOW, SlabAcc, first_fold, fold_into,
                               slab_plan)

JOB = "kanana"
D, HEADS, KV_LORA, NOPE, ROPE, V = 64, 4, 16, 16, 8, 16
EXPERTS, F_MOE, N_SHARED, F_DENSE, VOCAB, MOE_LAYERS = 8, 24, 2, 96, 256, 2
K = 5
N_EXAMPLES = [700, 4100, 1, 2500, 333]
#: the chips that share each layer in the share tie
S = 4


def layout():
    """(name, shape, axis): ``axis`` is where a chip's share is cut (experts
    by index, heads, vocabulary rows), None for what every chip holds
    whole."""
    out = [("embed", (VOCAB, D), 0)]
    for i in range(1 + MOE_LAYERS):
        p = f"layers.{i}."
        out += [(p + "input_layernorm", (D,), None),
                (p + "attn.q_proj", (D, HEADS * (NOPE + ROPE)), 1),
                (p + "attn.kv_a_proj_with_mqa", (D, KV_LORA + ROPE), None),
                (p + "attn.kv_a_layernorm", (KV_LORA,), None),
                (p + "attn.kv_b_proj", (KV_LORA, HEADS * (NOPE + V)), 1),
                (p + "attn.o_proj", (HEADS * V, D), 0),
                (p + "post_attention_layernorm", (D,), None)]
        if i == 0:
            out += [(p + "mlp.gate", (D, F_DENSE), None),
                    (p + "mlp.up", (D, F_DENSE), None),
                    (p + "mlp.down", (F_DENSE, D), None)]
        else:
            w = F_MOE * N_SHARED
            out += [(p + "moe.router", (D, EXPERTS), None),
                    (p + "moe.router_bias", (EXPERTS,), None),
                    (p + "moe.w_gate", (EXPERTS, D, F_MOE), 0),
                    (p + "moe.w_up", (EXPERTS, D, F_MOE), 0),
                    (p + "moe.w_down", (EXPERTS, F_MOE, D), 0),
                    (p + "moe.shared.gate", (D, w), None),
                    (p + "moe.shared.up", (D, w), None),
                    (p + "moe.shared.down", (w, D), None)]
    return out + [("norm", (D,), None), ("head", (D, VOCAB), 1)]


AXES = {name: axis for name, _, axis in layout()}


def tree(seed, dtype=jnp.bfloat16):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(AXES))
    return {name: (0.05 * jax.random.normal(k, shape)).astype(dtype)
            for k, (name, shape, _) in zip(keys, layout())}


def share(t, s):
    """Chip ``s``'s share of tree ``t``."""
    return {n: x if AXES[n] is None else jnp.split(x, S, AXES[n])[s]
            for n, x in t.items()}


def serve(updates, n_examples, global_model, *, resume_after=None):
    """One FedAvg round through the served path; with ``resume_after`` the
    aggregator is preempted after that many folds and a new one resumes
    from its checkpoint."""
    q = MessageQueue()
    for i, (u, n) in enumerate(zip(updates, n_examples)):
        q.publish_update(JOB, f"p{i}", u, 0, n)
    ex = AggregationExecutor(JOB, "fedavg", q)
    folded = 0
    if resume_after is not None:
        folded = ex.drain(0, max_messages=resume_after)
        ex.checkpoint()
        ex = AggregationExecutor(JOB, "fedavg", q)
        assert ex.resume()
    assert folded + ex.drain(0) == len(updates)
    return ex.finish_round(global_model, 0)


def reference(updates, n_examples, dtype=jnp.float32):
    """The weighted mean, summed in publish order in ``dtype`` (every step
    rounded to it) and rounded to bf16 once at the end."""
    ws = [float(max(n, 1)) for n in n_examples]
    r = lambda x: x.astype(dtype).astype(jnp.float32)  # noqa: E731

    def leaf(*us):
        acc = r(r(us[0]) * ws[0])
        for u, w in zip(us[1:], ws[1:]):
            acc = r(acc + r(r(u) * w))
        return r(acc / sum(ws)).astype(jnp.bfloat16)

    return jax.tree.map(leaf, *updates)


@pytest.fixture(scope="module")
def round_():
    updates = [tree(i) for i in range(K)]
    return updates, tree(99), serve(updates, N_EXAMPLES, tree(99))


@pytest.mark.parametrize("precision", ["float32", "bfloat16"])
def test_served_round_against_the_plain_reference(round_, precision):
    """fp32: equal bit for bit, every leaf bf16 in the global model's tree.
    The bf16-accumulating control is off by more than the benchmark's
    limit, 1e-5 of a leaf's largest magnitude."""
    updates, model, got = round_
    want = reference(updates, N_EXAMPLES, jnp.dtype(precision))
    assert jax.tree.structure(got) == jax.tree.structure(model)
    gaps = []
    for g, w, m in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                       jax.tree.leaves(model)):
        assert g.dtype == jnp.bfloat16 and g.shape == m.shape
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        gaps.append(np.max(np.abs(g - w)) / np.max(np.abs(w)))
    if precision == "float32":
        assert max(gaps) == 0.0
    else:
        assert max(gaps) > 1e-5


def test_shares_fuse_to_the_share_of_the_uncut_round(round_):
    """Each of S chips fuses its share of every update; the shares put back
    along their axes equal the uncut round, and what every chip holds whole
    comes out the same on each."""
    updates, model, uncut = round_
    parts = [serve([share(u, s) for u in updates], N_EXAMPLES,
                   share(model, s)) for s in range(S)]
    for name, axis in AXES.items():
        got = [np.asarray(p[name], np.float32) for p in parts]
        if axis is None:
            for g in got:
                np.testing.assert_array_equal(g, got[0])
            whole = got[0]
        else:
            whole = np.concatenate(got, axis)
        np.testing.assert_array_equal(
            whole, np.asarray(uncut[name], np.float32), err_msg=name)


@pytest.mark.parametrize("resume_after", [1, 3])
def test_checkpoint_and_resume_mid_round(round_, resume_after):
    updates, model, uninterrupted = round_
    got = serve(updates, N_EXAMPLES, model, resume_after=resume_after)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(uninterrupted)):
        assert g.dtype == w.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and the jaxprs nested in it, but not
    inside a Pallas kernel's body."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for j in (v if isinstance(v, (list, tuple)) else [v]):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    yield from _eqns(j)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_fold_stages_the_update_at_its_own_width(dtype):
    """The fold reads a bf16 update as bf16: the packed slab is
    concatenated in bf16 from exactly the leaves under ``PACK_BELOW``, the
    whole leaves are read where they lie, and each slab is upcast only
    where its multiply-add reads it, so no fp32 copy of the update is
    staged. The first fold multiplies in fp32. An fp32 update's folds have
    no cast."""
    # the layout's leaves and one as wide as a full-vocabulary head
    wide = (0.05 * jax.random.normal(jax.random.PRNGKey(7), (VOCAB * 2, D * 8))
            ).astype(dtype)
    leaves = jax.tree.leaves(tree(0, dtype)) + [wide]
    plan = slab_plan(tuple(x.shape for x in leaves))
    small = [x for x in leaves if x.size < PACK_BELOW]
    assert plan.whole == (len(leaves) - 1,) and len(small) == len(leaves) - 1
    n_small = sum(x.size for x in small)
    acc = SlabAcc((jnp.zeros(wide.shape), jnp.zeros((n_small,))),
                  jax.tree.structure(leaves), plan)

    def read_at_own_width(eqns, n_slabs):
        (concat,) = [e for e in eqns if e.primitive.name == "concatenate"]
        assert concat.outvars[0].aval.shape == (n_small,)
        assert concat.outvars[0].aval.dtype == dtype
        assert [v.aval.size for v in concat.invars] == [x.size for x in small]
        assert all(v.aval.dtype == dtype for v in concat.invars)
        upcasts = [e for e in eqns if e.primitive.name == "convert_element_type"]
        assert len(upcasts) == n_slabs * (dtype != jnp.float32)
        for up in upcasts:
            assert up.invars[0].aval.dtype == dtype
            (reader,) = [e for e in eqns if up.outvars[0] in e.invars]
            assert reader.primitive.name == "mul"
        muls = [e for e in eqns if e.primitive.name == "mul"]
        assert len(muls) == n_slabs
        assert all(v.aval.dtype == jnp.float32 for e in muls for v in e.invars)

    fold = jax.make_jaxpr(fold_into)(acc, leaves, np.float32(1.0))
    read_at_own_width(list(_eqns(fold.jaxpr)), 2)
    first = jax.make_jaxpr(first_fold)(leaves, np.float32(2.0))
    read_at_own_width(list(_eqns(first.jaxpr)), 2)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_fold_span_says_what_the_fold_read(tmp_path, dtype):
    """``repro.fold`` carries the update's dtype and bytes, and the
    accumulator's slabs and packed leaves."""
    updates = [tree(i, dtype) for i in range(2)]
    jax.block_until_ready(serve(updates, [1, 2], tree(99, dtype)))  # warm
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(serve(updates, [1, 2], tree(99, dtype)))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    folds = [dict(e.stats) for p in jax.profiler.ProfileData.from_file(
        path).planes for line in p.lines for e in line.events
        if e.name == "repro.fold"]
    nbytes = sum(x.nbytes for x in jax.tree.leaves(updates[0]))
    assert nbytes == sum(math.prod(s) for _, s, _ in layout()) * (
        jnp.dtype(dtype).itemsize)
    plan = slab_plan(tuple(s for _, s, _ in layout()))
    assert folds == [{"dtype": jnp.dtype(dtype).name, "nbytes": nbytes,
                      "slabs": plan.n_slabs,
                      "packed": len(plan.packed)}] * 2
