"""Party runtime: real local training in JAX (weights for FedAvg/FedProx,
gradients for FedSGD), with the timing measurements that §5.2 requires
parties to report (epoch time, minibatch time, dataset size)."""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.data.loader import Loader
from repro.models import model as M
from repro.optim import sgd

Pytree = Any


@dataclasses.dataclass
class LocalResult:
    update: Pytree  # weights (fedavg/fedprox) or gradients (fedsgd)
    n_examples: int
    train_time_s: float  # measured wall time (what the party reports)
    minibatch_time_s: float
    loss: float


@functools.lru_cache(maxsize=None)
def _local_steps(cfg: ModelConfig, algorithm: str, lr: float,
                 prox_mu: float) -> Tuple[Any, Any, Any]:
    """(optimizer, jitted train step, jitted gradient step), shared by every
    party of a job: the parties agree on the model and hyperparameters, so
    the step compiles once per process, not once per party."""
    opt = sgd(lr)

    def party_loss(params, batch, global_params):
        loss, metrics = M.loss_fn(cfg, params, batch)
        if algorithm == "fedprox" and prox_mu > 0:
            # FedProx: + mu/2 * ||w - w_global||^2 on the PARTY objective
            sq = sum(
                jnp.sum(jnp.square(p.astype(jnp.float32) -
                                   g.astype(jnp.float32)))
                for p, g in zip(jax.tree.leaves(params),
                                jax.tree.leaves(global_params))
            )
            loss = loss + 0.5 * prox_mu * sq
        return loss, metrics

    def step(params, opt_state, batch, global_params):
        (loss, _), grads = jax.value_and_grad(
            party_loss, has_aux=True
        )(params, batch, global_params)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    def gstep(params, batch):
        (loss, _), grads = jax.value_and_grad(
            lambda p: M.loss_fn(cfg, p, batch), has_aux=True
        )(params)
        return grads, loss

    return opt, jax.jit(step), jax.jit(gstep)


class Party:
    def __init__(
        self,
        party_id: str,
        cfg: ModelConfig,
        data: Dict[str, np.ndarray],
        *,
        algorithm: str = "fedavg",
        batch_size: int = 16,
        lr: float = 0.05,
        prox_mu: float = 0.0,
        seed: int = 0,
    ):
        self.party_id = party_id
        self.cfg = cfg
        self.algorithm = algorithm
        self.loader = Loader(data, batch_size, seed=seed)
        self.n_examples = self.loader.n
        self.lr = lr
        self.prox_mu = prox_mu

    def _steps(self):
        """(optimizer, train step, gradient step) for the current settings."""
        return _local_steps(self.cfg, self.algorithm, float(self.lr),
                            float(self.prox_mu))

    # ---- §5.2 timing report: measure one minibatch (post-compilation) ---------
    def calibrate(self, global_params: Pytree) -> Tuple[float, float]:
        """Returns (minibatch_time_s, epoch_time_s estimate)."""
        opt, step, grad = self._steps()
        batch = _to_jnp(next(self.loader.epoch(shuffle=False)))
        opt_state = opt.init(global_params)
        # warmup (compile)
        if self.algorithm == "fedsgd":
            grad(global_params, batch)
            t0 = time.perf_counter()
            jax.block_until_ready(grad(global_params, batch))
        else:
            step(global_params, opt_state, batch, global_params)
            t0 = time.perf_counter()
            jax.block_until_ready(
                step(global_params, opt_state, batch, global_params)
            )
        t_mb = time.perf_counter() - t0
        return t_mb, t_mb * len(self.loader)

    # ---- one FL round of local work ----------------------------------------------
    def local_round(self, global_params: Pytree, epochs: int = 1
                    ) -> LocalResult:
        opt, step, grad = self._steps()
        t0 = time.perf_counter()
        n_batches = 0
        last_loss = 0.0
        if self.algorithm == "fedsgd":
            # one pass, average gradients (classic FedSGD)
            acc = None
            for batch in self.loader.epoch():
                grads, loss = grad(global_params, _to_jnp(batch))
                acc = grads if acc is None else jax.tree.map(
                    jnp.add, acc, grads
                )
                n_batches += 1
                last_loss = float(loss)
            update = jax.tree.map(lambda g: g / n_batches, acc)
        else:
            params = global_params
            opt_state = opt.init(params)
            for _ in range(epochs):
                for batch in self.loader.epoch():
                    params, opt_state, loss = step(
                        params, opt_state, _to_jnp(batch), global_params
                    )
                    n_batches += 1
                    last_loss = float(loss)
            update = params
        jax.block_until_ready(jax.tree.leaves(update)[0])
        dt = time.perf_counter() - t0
        return LocalResult(
            update=update,
            n_examples=self.n_examples,
            train_time_s=dt,
            minibatch_time_s=dt / max(n_batches, 1),
            loss=last_loss,
        )


def _to_jnp(batch: Dict[str, np.ndarray]) -> Dict[str, jax.Array]:
    return {k: jnp.asarray(v) for k, v in batch.items()}
