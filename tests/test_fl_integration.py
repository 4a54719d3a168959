"""Integration: real parties (JAX training) + queue + aggregator executor +
the end-to-end FLJobRuntime (learning + scheduling fidelity together)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.jobspec import FLJobSpec, PartySpec
from repro.core.queue import MessageQueue
from repro.data import SyntheticLM, SyntheticLMConfig
from repro.fl.aggregator import AggregationExecutor
from repro.fl.job import FLJobRuntime
from repro.fl.party import Party
from repro.models import model as M

configs.load_all()


def tiny_cfg(**kw):
    return configs.get_config("qwen3-0.6b").reduced(
        num_layers=2, d_model=64, vocab_size=128, **kw
    )


def make_party(pid, cfg, n_seq=32, algorithm="fedavg", seed=0):
    data_cfg = SyntheticLMConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                 n_domains=4)
    lm = SyntheticLM(data_cfg, seed=0)
    ds = lm.make_dataset(np.full(4, 0.25), n_seq, seed=seed)
    return Party(pid, cfg, ds, algorithm=algorithm, batch_size=8, lr=0.05,
                 seed=seed)


def test_party_local_round_fedavg_changes_weights():
    cfg = tiny_cfg()
    p = make_party("p0", cfg)
    gp = M.init(cfg, jax.random.PRNGKey(0))
    res = p.local_round(gp)
    assert res.n_examples == 32
    assert res.train_time_s > 0
    moved = any(
        not np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))
        for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(res.update))
    )
    assert moved


def test_party_fedsgd_returns_gradients():
    cfg = tiny_cfg()
    p = make_party("p0", cfg, algorithm="fedsgd")
    gp = M.init(cfg, jax.random.PRNGKey(0))
    res = p.local_round(gp)
    # gradients are small relative to weights, and are NOT the weights
    gnorm = float(
        jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                     for g in jax.tree.leaves(res.update)))
    )
    assert 0 < gnorm < 1e4


def test_fedprox_mu_shrinks_drift():
    cfg = tiny_cfg()
    gp = M.init(cfg, jax.random.PRNGKey(0))

    def drift(mu):
        p = make_party("p0", cfg, algorithm="fedprox", seed=1)
        p.prox_mu = mu
        res = p.local_round(gp, epochs=2)
        return float(
            jnp.sqrt(sum(
                jnp.sum(jnp.square(a.astype(jnp.float32) -
                                   b.astype(jnp.float32)))
                for a, b in zip(jax.tree.leaves(res.update),
                                jax.tree.leaves(gp))
            ))
        )

    assert drift(mu=1.0) < drift(mu=0.0)


def test_aggregator_queue_roundtrip_and_preemption():
    cfg = tiny_cfg()
    q = MessageQueue()
    agg = AggregationExecutor("job", "fedavg", q)
    gp = M.init(cfg, jax.random.PRNGKey(0))
    updates = []
    for i in range(4):
        u = jax.tree.map(
            lambda p, i=i: p + (0.1 * (i + 1)), gp
        )
        updates.append(u)
        q.publish_update("job", f"p{i}", u, round_idx=0, n_examples=10)

    # drain first two, preempt (checkpoint), resume in a NEW executor
    n = agg.drain(0, max_messages=2)
    assert n == 2
    agg.checkpoint()
    agg2 = AggregationExecutor("job", "fedavg", q)
    assert agg2.resume()
    n2 = agg2.drain(0)
    assert n2 == 2
    fused_model = agg2.finish_round(gp, 0)
    # equal weights -> mean shift of +0.25
    want = jax.tree.map(lambda p: p + 0.25, gp)
    for a, b in zip(jax.tree.leaves(fused_model), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-2, atol=1e-2)
    # fused model published per round
    assert len(q.topic("fused/job")) == 1


def test_checkpoint_outlives_the_folds_that_donate_the_accumulator():
    """A fold donates the accumulator, so a checkpoint holds a device copy:
    an executor checkpoints, keeps folding and finishes; a second executor
    resumes from the snapshot and finishes; both publish the direct result.
    A state that was folded from is spent and raises when used."""
    cfg = tiny_cfg()
    gp = M.init(cfg, jax.random.PRNGKey(0))
    q = MessageQueue()
    nex = [10, 20, 30, 40]
    ups = [jax.tree.map(lambda p, i=i: p * (1 + 0.01 * i) + 0.01 * i, gp)
           for i in range(4)]
    for i, u in enumerate(ups):
        q.publish_update("job", f"p{i}", u, round_idx=0, n_examples=nex[i])
    direct = AggregationExecutor("direct", "fedavg").aggregate(ups, nex, gp)

    first = AggregationExecutor("job", "fedavg", q, group="first")
    assert first.drain(0, max_messages=2) == 2
    first.checkpoint()
    at_checkpoint = first.state
    assert first.drain(0) == 2
    with pytest.raises(RuntimeError, match="folded or merged"):
        at_checkpoint.result()
    kept_folding = first.finish_round(gp, 0)

    second = AggregationExecutor("job", "fedavg", q, group="second")
    q.topic("updates/job").commit("second", 1)  # the snapshot's offset
    assert second.resume()
    assert second.drain(0) == 2
    resumed = second.finish_round(gp, 0)
    # resume folded into a copy: the snapshot still holds the first two
    third = AggregationExecutor("job", "fedavg", q)
    assert third.resume()
    partial = AggregationExecutor("p", "fedavg").aggregate(ups[:2], nex[:2])
    for x, y in zip(jax.tree.leaves(third.state.result()),
                    jax.tree.leaves(partial)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    for got in (kept_folding, resumed):
        assert jax.tree.structure(got) == jax.tree.structure(direct)
        for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(direct)):
            assert x.dtype == y.dtype
            np.testing.assert_array_equal(np.asarray(x, np.float32),
                                          np.asarray(y, np.float32))


def test_parallel_workers_equal_single_worker():
    cfg = tiny_cfg()
    gp = M.init(cfg, jax.random.PRNGKey(0))
    ups = [jax.tree.map(lambda p, i=i: p * (1 + 0.01 * i), gp)
           for i in range(5)]
    nex = [10, 20, 30, 40, 50]
    a1 = AggregationExecutor("j", "fedavg", n_workers=1).aggregate(ups, nex)
    a3 = AggregationExecutor("j", "fedavg", n_workers=3).aggregate(ups, nex)
    for x, y in zip(jax.tree.leaves(a1), jax.tree.leaves(a3)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), rtol=1e-4,
                                   atol=1e-5)


@pytest.mark.slow
def test_fljob_runtime_end_to_end_converges_and_schedules():
    cfg = tiny_cfg()
    spec = FLJobSpec(
        job_id="it", model_arch=cfg.name, model_bytes=M.n_params(cfg) * 4,
        aggregation_algorithm="fedavg", rounds=4, lr=0.05, batch_size=8,
        parties={f"p{i}": PartySpec(f"p{i}") for i in range(3)},
    )
    rt = FLJobRuntime(cfg, spec, n_sequences=96, heterogeneous=True, seed=0,
                      eval_sequences=24)
    loss0 = rt.eval_loss()
    recs = rt.run(verbose=False)
    assert len(recs) == 4
    assert recs[-1].global_loss < loss0  # learning happened
    # scheduling: predictions converge (round >= 2 uses observed times)
    last = recs[-1]
    actual = max(last.arrivals.values())
    assert abs(last.t_rnd_pred - actual) / actual < 0.5
    assert last.latency < 30.0
    assert last.container_seconds > 0
