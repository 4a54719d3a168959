"""The streaming fold's slab accumulator, on the CPU.

Leaves of ``PACK_BELOW`` elements or more are slabs of their own, in their
own shape; the smaller ones lie end to end in one packed 1-D slab. A round
through the served path (``MessageQueue`` -> ``AggregationExecutor.drain``
-> ``finish_round``) must equal a plain ``jax.numpy`` fp32 reference leaf
by leaf, whichever side of the threshold a leaf is on; merging partial
aggregates and resuming from a checkpoint must give the model one
uninterrupted fold gives; ``repro.fold`` must say how the accumulator is
laid out.
"""
import glob
import json
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.queue import MessageQueue
from repro.fl.aggregator import AggregationExecutor
from repro.fl.fusion import PACK_BELOW, FusionState, get_algorithm, slab_plan

_CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "chipbench" / "configs"
JOB = "slabs"
N_EXAMPLES = [700, 4100, 1, 2500]
LR = 0.3

#: leaf shapes: on both sides of the threshold, all at or above it, all below
TREES = {
    "mixed": {"conv": (3, 3, 64, 128), "bias": (128,),
              "fc": [(300, 256), (17,)], "scale": (), "embed": (50, 40)},
    "whole": {"conv": (3, 3, 64, 128), "fc": [(300, 256)]},
    "packed": {"bias": (128,), "fc": [(9, 130), (37,)], "scale": ()},
}


def _tree(kind, seed, dtype):
    shapes, treedef = jax.tree.flatten(TREES[kind],
                                       is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return jax.tree.unflatten(treedef, [
        jax.random.normal(k, s).astype(dtype) for k, s in zip(keys, shapes)])


def _serve(updates, n_examples, model, alg, *, resume_after=None):
    """One round through the served path; with ``resume_after`` the
    aggregator is preempted after that many folds and a new one resumes
    from its checkpoint."""
    q = MessageQueue()
    for i, (u, n) in enumerate(zip(updates, n_examples)):
        q.publish_update(JOB, f"p{i}", u, 0, n)
    ex = AggregationExecutor(JOB, alg, q)
    folded = 0
    if resume_after is not None:
        folded = ex.drain(0, max_messages=resume_after)
        ex.checkpoint()
        ex = AggregationExecutor(JOB, alg, q)
        assert ex.resume()
    assert folded + ex.drain(0) == len(updates)
    return ex.finish_round(model, 0, LR)


#: each step of one leaf compiled as one program, as the served path
#: compiles it: a backend that contracts ``a + w*u`` into a fused
#: multiply-add (XLA's CPU backend does) then contracts it on both sides
_first = jax.jit(lambda u, w: w * u.astype(jnp.float32))
_step = jax.jit(lambda a, u, w: a + w * u.astype(jnp.float32))
_finish = {
    "fedavg": jax.jit(lambda a, tw, g, lr: (a / tw).astype(g.dtype)),
    "fedsgd": jax.jit(lambda a, tw, g, lr: (
        g.astype(jnp.float32) - lr * (a / tw)).astype(g.dtype)),
}


def _reference(updates, n_examples, model, alg):
    """The new global model, leaf by leaf in plain ``jax.numpy``: the
    updates summed in fp32 in publish order, over the total weight, the
    algorithm's step, rounded to the model's dtype once."""
    ws = [float(max(n, 1)) for n in n_examples]

    def leaf(g, *us):
        acc = _first(us[0], ws[0])
        for u, w in zip(us[1:], ws[1:]):
            acc = _step(acc, u, w)
        return _finish[alg](acc, sum(ws), g, LR)

    return jax.tree.map(leaf, model, *updates)


@pytest.mark.parametrize("config,slabs", [
    ("vgg16", 15), ("effnetb7", 128), ("kanana2-30b", 54)])
def test_slab_counts_at_benchmark_leaves(config, slabs):
    """The plan at each benchmark configuration's leaf list: the packed
    slab holds exactly the leaves under the threshold, in leaf order."""
    cfg = json.loads((_CONFIGS / f"{config}.json").read_text())
    shapes = tuple(tuple(s) for _, s in cfg["leaves"])
    plan = slab_plan(shapes)
    assert plan.n_slabs == slabs
    assert plan.packed == tuple(i for i, s in enumerate(shapes)
                                if math.prod(s) < PACK_BELOW)
    assert plan.whole == tuple(i for i, s in enumerate(shapes)
                               if math.prod(s) >= PACK_BELOW)


@pytest.mark.parametrize("kind,dtype,alg", [
    ("mixed", jnp.float32, "fedavg"), ("mixed", jnp.float32, "fedsgd"),
    ("mixed", jnp.bfloat16, "fedavg"), ("mixed", jnp.bfloat16, "fedsgd"),
    ("whole", jnp.float32, "fedsgd"), ("packed", jnp.bfloat16, "fedavg")])
def test_served_round_equals_the_plain_reference(kind, dtype, alg):
    """Equal bit for bit, in the global model's tree, shapes and dtypes."""
    updates = [_tree(kind, i, dtype) for i in range(len(N_EXAMPLES))]
    model = _tree(kind, 99, dtype)
    got = _serve(updates, N_EXAMPLES, model, alg)
    want = _reference(updates, N_EXAMPLES, model, alg)
    assert jax.tree.structure(got) == jax.tree.structure(model)
    for g, w, m in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                       jax.tree.leaves(model)):
        assert g.shape == m.shape and g.dtype == m.dtype
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


def _integer_tree(seed):
    """Integer-valued fp32 leaves: with integer weights every product and
    partial sum is exact, so any grouping of the sum is the same number."""
    rng = np.random.default_rng(seed)
    return jax.tree.map(
        lambda t: jnp.asarray(rng.integers(-64, 65, t), jnp.float32),
        TREES["mixed"], is_leaf=lambda x: isinstance(x, tuple))


@pytest.mark.parametrize("how", ["merge", "resume"])
def test_merge_and_resume_equal_one_uninterrupted_fold(how):
    updates = [_integer_tree(i) for i in range(len(N_EXAMPLES))]
    model = _integer_tree(99)
    alg = get_algorithm("fedsgd")
    uninterrupted = _serve(updates, N_EXAMPLES, model, "fedsgd")
    if how == "resume":
        got = _serve(updates, N_EXAMPLES, model, "fedsgd", resume_after=2)
    else:
        ws = [alg.weight_of(n) for n in N_EXAMPLES]
        parts = [FusionState(), FusionState()]
        for i, (u, w) in enumerate(zip(updates, ws)):
            parts[i % 2] = parts[i % 2].fold(u, w)
        got = parts[1].merge(parts[0]).finish(alg, model, LR)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(uninterrupted)):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_fold_span_says_how_the_accumulator_is_laid_out(tmp_path):
    """``repro.fold`` carries ``slabs`` (the accumulator's buffers) and
    ``packed`` (the leaves in its packed slab) beside what the fold
    read."""
    updates = [_tree("mixed", i, jnp.bfloat16) for i in range(2)]
    model = _tree("mixed", 99, jnp.bfloat16)
    jax.block_until_ready(_serve(updates, [1, 2], model, "fedavg"))  # warm
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(_serve(updates, [1, 2], model, "fedavg"))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    folds = [dict(e.stats) for p in jax.profiler.ProfileData.from_file(
        path).planes for line in p.lines for e in line.events
        if e.name == "repro.fold"]
    nbytes = sum(x.nbytes for x in jax.tree.leaves(updates[0]))
    assert folds == [{"dtype": "bfloat16", "nbytes": nbytes, "slabs": 3,
                      "packed": 4}] * 2
