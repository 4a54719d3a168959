"""Set-up and the measured window: the aggregator's served path.

Each round is one just-in-time deployment, as in the paper: a fresh
``AggregationExecutor`` on a fresh ``MessageQueue``. A round publishes its
K updates (``MessageQueue.publish_update``), drains them
(``AggregationExecutor.drain``: ``FusionState.fold`` ->
``kernels.accumulate`` -> ``pair_fuse``), finishes (``finish_round``:
``FusionState.result``, ``FusionAlgorithm.apply``, ``publish_fused``), and
is timed to the published model being ready on the device. The queue keeps
every message on the device for its lifetime, so one queue for the whole
window would fill the chip's memory with published models within a few
dozen rounds; the harness keeps only the last global model and the rounds
sampled for the check.

The K party updates are made on the device from the seed at set-up and
reused by every round, in a seeded order and with seeded weights: every
fold still reads its update from HBM, so reuse changes no work, while
every round's fused model differs.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import traffic as traffic_mod

JOB = "chipbench"
#: how the updates and the global model are drawn (config "assumed")
VALUE_STD = 0.05
#: rounds, besides the last, kept for the correctness check
SAMPLED_ROUNDS = 2


def span(name: str, **kw):
    """A host span on the profiler's clock (a no-op while not tracing)."""
    return jax.profiler.TraceAnnotation("chipbench." + name, **kw)


def seed_words(seed: int, n: int) -> List[int]:
    """``n`` 32-bit words from a seed of any size."""
    return [int(x) for x in np.random.SeedSequence(seed).generate_state(n)]


def make_inputs(config: dict, k: int, seed: int):
    """K party updates and the global model, drawn on the device from the
    seed by one jitted program (one call per party), in the configuration's
    dtype."""
    shapes = [tuple(s) for _, s in config["leaves"]]
    sizes = [math.prod(s) for s in shapes]
    dtype = jnp.dtype(config["dtype"])

    @jax.jit
    def draw(key):
        flat = jax.random.normal(key, (sum(sizes),), jnp.float32) * VALUE_STD
        out, off = [], 0
        for shape, n in zip(shapes, sizes):
            out.append(flat[off:off + n].reshape(shape).astype(dtype))
            off += n
        return out

    key = jax.random.wrap_key_data(
        jnp.asarray(seed_words(seed, 2), jnp.uint32), impl="threefry2x32")
    keys = jax.random.split(key, k + 1)
    trees = [draw(keys[i]) for i in range(k + 1)]
    jax.block_until_ready(trees)
    return trees[:k], trees[k]


class CompileLog:
    """Backend compiles and persistent-cache loads, by host clock."""

    def __init__(self):
        self.events: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in ("/jax/core/compile/backend_compile_duration",
                     "/jax/compilation_cache/cache_retrieval_time_sec"):
            self.events.append(time.perf_counter())

    def count(self, t0: float, t1: float = math.inf) -> int:
        return sum(t0 <= t < t1 for t in self.events)


@dataclasses.dataclass
class Sampled:
    round_idx: int
    order: np.ndarray
    n_examples: np.ndarray
    prev: list
    published: list


class Sampler:
    """Keeps a seeded uniform sample of the rounds (reservoir) and the last
    one, for the check after the window."""

    def __init__(self, seed: int, size: int = SAMPLED_ROUNDS):
        self.rng = np.random.default_rng(np.random.SeedSequence([seed, 2]))
        self.size = size
        self.kept: List[Sampled] = []
        self.last: Optional[Sampled] = None
        self.seen = 0

    def offer(self, s: Sampled) -> None:
        if self.last is not None:
            self._reservoir(self.last)
        self.last = s

    def _reservoir(self, s: Sampled) -> None:
        self.seen += 1
        if len(self.kept) < self.size:
            self.kept.append(s)
        else:
            j = int(self.rng.integers(self.seen))
            if j < self.size:
                self.kept[j] = s

    def rounds(self) -> List[Sampled]:
        return self.kept + ([self.last] if self.last is not None else [])


@dataclasses.dataclass
class RoundRecord:
    round_idx: int
    due: float       # host clock: when the round's last update was due
    ready: float     # host clock: the published model ready on the device
    drained: int     # updates the drains folded
    published: int   # fused models the queue holds for the round


@dataclasses.dataclass
class WindowResult:
    t0: float
    t_close: float
    rounds: List[RoundRecord]
    lateness: np.ndarray  # per update: publish time - due time, seconds
    sampler: Sampler


def default_executor(config: dict) -> Callable:
    from repro.fl.aggregator import AggregationExecutor

    return lambda queue: AggregationExecutor(JOB, config["algorithm"], queue)


def serve_round(r: int, plan, updates, global_model, executor_of, lr: float,
                drain_each: bool, due_of: Callable[[int], float],
                lateness: List[float]):
    """One round through the served path; returns (record, published)."""
    from repro.core.queue import MessageQueue

    queue = MessageQueue()
    ex = executor_of(queue)
    drained = 0
    k = len(plan.order)
    with span("round", round=r):
        for i in range(k):
            due = due_of(i)
            now = time.perf_counter()
            if due > now:
                time.sleep(due - now)
                now = time.perf_counter()
            lateness.append(now - due)
            with span("publish"):
                queue.publish_update(JOB, f"p{plan.order[i]}",
                                     updates[plan.order[i]], r,
                                     int(plan.n_examples[i]), timestamp=due)
            if drain_each or i == k - 1:
                with span("drain"):
                    drained += ex.drain(r)
        with span("finish_round"):
            ex.finish_round(global_model, r, lr)
        fused = queue.topic(f"fused/{JOB}").poll("chipbench-check")
        published = fused[-1].value if fused else None
        with span("wait"):
            jax.block_until_ready(published)
    ready = time.perf_counter()
    rec = RoundRecord(r, due_of(k - 1), ready, drained,
                      sum(m.key == str(r) for m in fused))
    return rec, published


def run_window(config: dict, traffic: traffic_mod.Traffic, updates: list,
               global_model: list, seconds: float, *,
               executor_of: Optional[Callable] = None,
               trace_dir: Optional[str] = None,
               trace_seconds: float = 0.0) -> WindowResult:
    """Serve rounds for ``seconds``: a closed loop starts rounds until the
    window closes; an open loop serves every round whose updates are due
    in it. With ``trace_dir`` the profiler records the rounds completed in
    the first ``trace_seconds``."""
    executor_of = executor_of or default_executor(config)
    sampler = Sampler(traffic.seed)
    lr = float(config.get("server_lr", 1.0))
    rounds: List[RoundRecord] = []
    lateness: List[float] = []
    tracing = trace_dir is not None
    if tracing:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # no event per Python call
        opts.host_tracer_level = 1    # the harness's spans, not the runtime's
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.perf_counter()
    r = 0
    try:
        while True:
            if traffic.closed and time.perf_counter() - t0 >= seconds:
                break
            if not traffic.closed and r >= traffic.n_rounds:
                break
            plan = traffic.round(r)
            if traffic.closed:
                t_start = time.perf_counter()
                due_of = lambda i, t=t_start: t  # noqa: E731
            else:
                due_of = lambda i, p=plan: t0 + float(p.due[i])  # noqa: E731
            prev = global_model
            rec, published = serve_round(r, plan, updates, global_model,
                                         executor_of, lr, traffic.drain_each,
                                         due_of, lateness)
            rounds.append(rec)
            sampler.offer(Sampled(r, plan.order, plan.n_examples, prev,
                                  published))
            global_model = published
            r += 1
            if tracing and rec.ready - t0 >= trace_seconds:
                jax.profiler.stop_trace()
                tracing = False
    finally:
        if tracing:
            jax.profiler.stop_trace()
    return WindowResult(t0, t0 + seconds, rounds, np.asarray(lateness),
                        sampler)


def warm_up(config: dict, traffic: traffic_mod.Traffic, updates: list,
            global_model: list, executor_of: Optional[Callable] = None):
    """One untimed round of the cell's own shapes: compiles every program
    the window runs."""
    executor_of = executor_of or default_executor(config)
    plan = traffic_mod.RoundPlan(np.arange(traffic.k),
                                 np.ones(traffic.k, np.int64), None)
    now = time.perf_counter()
    serve_round(-1, plan, updates, global_model, executor_of,
                float(config.get("server_lr", 1.0)), traffic.drain_each,
                lambda i: now, [])
