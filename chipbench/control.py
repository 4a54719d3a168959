"""The control: the plain reference in the aggregator's place.

``ReferenceExecutor`` serves a round as ``AggregationExecutor`` does, from
the same queue, but fuses it with ``reference.reference_round`` in a
stated precision. Computed in bfloat16, one precision below the
configurations' float32, the benchmark's check has to refuse it
(``calibrate.py limits`` reads it on the chip; ``tests/test_harness.py``
at a small size).
"""
from __future__ import annotations

import jax.numpy as jnp

from chipbench import reference
from chipbench.loop import JOB


class ReferenceExecutor:
    group = "aggregator"

    def __init__(self, queue, algorithm: str, compute_dtype=jnp.bfloat16):
        self.queue, self.algorithm = queue, algorithm
        self.compute_dtype = compute_dtype
        self.updates, self.n_examples = [], []

    def drain(self, round_idx: int) -> int:
        topic = self.queue.topic(f"updates/{JOB}")
        n = 0
        for m in topic.poll(self.group):
            if m.value["round"] == round_idx:
                self.updates.append(m.value["update"])
                self.n_examples.append(m.value["n_examples"])
                n += 1
            topic.commit(self.group, m.offset)
        return n

    def finish_round(self, global_model, round_idx: int, lr: float = 1.0):
        new = reference.reference_round(self.updates, self.n_examples,
                                        global_model, lr, self.algorithm,
                                        self.compute_dtype)
        self.queue.publish_fused(JOB, round_idx, new)
        self.updates, self.n_examples = [], []
        return new


def executor_of(config: dict, compute_dtype=jnp.bfloat16):
    return lambda queue: ReferenceExecutor(queue, config["algorithm"],
                                           compute_dtype)
