#!/usr/bin/env python3
"""Bring-up check: the real-training federated round on one TPU chip.

    python chip_smoke.py            # on a machine with one TPU chip

Drives the system's main path once through its public entry point,
``Platform().train``, on the full ``example-100m`` config (88.1M
parameters; random weights from a seed): 4 FedProx parties train locally
for 2 rounds on the chip and publish their bf16 updates to the message
queue, and the aggregator folds each update into its fp32 slab
accumulator (one XLA multiply-add per slab) and publishes the fused model.
Then, on what the run left in the queue:

  a  train+fuse   each round's fp32 fold against a plain jax.numpy fp32
                  weighted mean of that round's updates, and the published
                  model equal to that fold cast by ``FusionAlgorithm.apply``
  b  resume       a fresh consumer group drains part of round 0,
                  checkpoints, resumes in a new executor and drains the
                  rest: the result equals the uninterrupted fold exactly
  c  drains       the batch (``fused_agg``) and int8 (``quant_agg``) drains
                  of K=8 retained updates against ``repro.kernels.ref``
  d  kernels      each of the three kernels compiles to a Mosaic
                  ``tpu_custom_call`` (none runs in the interpreter)

It prints one line per phase (wall time, error against the reference and
its tolerance), the compile time of set-up plus round 0 against round 1,
and the device's peak memory; its last line is one JSON object naming the
device. Without a TPU, or when any phase fails, it exits non-zero and
prints no such line. The phase functions also run on the CPU at a reduced
size (``tests/test_chip_smoke.py``).
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

#: |fold - reference| bound for the fp32 streaming fold, relative to
#: max(1, max|reference|): a few fp32 roundings, far below one bf16 step
FP32_RTOL = 1e-5
#: the batch drain returns the updates' dtype (bf16), as does the oracle:
#: per leaf they may differ by one bf16 rounding step (2^-7 relative)
BF16_RTOL = 2.0 ** -7
#: the int8 drain's fp32 result against the oracle, per leaf, relative to
#: the leaf's max|ref|
INT8_RTOL = 1e-5

JOB_ID = "chip-smoke"
K_DRAIN = 8


def _max_abs(a, b) -> float:
    """Largest |a - b| over all leaves; NaN if any value is not finite."""
    return float(np.max([jnp.max(jnp.abs(x.astype(jnp.float32)
                                         - y.astype(jnp.float32)))
                         for x, y in zip(jax.tree.leaves(a),
                                         jax.tree.leaves(b))]))


def _max_mag(tree) -> float:
    return float(np.max([jnp.max(jnp.abs(x.astype(jnp.float32)))
                         for x in jax.tree.leaves(tree)]))


def _err_over_tol(got, want, rtol: float) -> tuple:
    """(largest |got - want|, largest per-leaf ratio of that error to
    ``rtol`` times the leaf's largest |want|); NaN if not finite."""
    errs = [_max_abs(g, w) for g, w in zip(got, want)]
    tols = [rtol * _max_mag(w) for w in want]
    return (float(np.max(errs)),
            float(np.max([e / max(t, 1e-30) for e, t in zip(errs, tols)])))


def _bitwise_equal(a, b) -> bool:
    return all(bool(jnp.array_equal(x, y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def reference_mean(updates, weights):
    """Plain jax.numpy fp32 weighted mean of model updates."""
    acc = None
    for u, w in zip(updates, weights):
        t = jax.tree.map(lambda x: x.astype(jnp.float32) * w, u)
        acc = t if acc is None else jax.tree.map(jnp.add, acc, t)
    total = sum(weights)
    return jax.tree.map(lambda a: a / total, acc)


def _updates(runtime, round_idx=None):
    """(updates, n_examples) retained in the queue, in publish order."""
    msgs = runtime.queue.topic(f"updates/{JOB_ID}").poll("chip-smoke-read")
    msgs = [m for m in msgs
            if round_idx is None or m.value["round"] == round_idx]
    return ([m.value["update"] for m in msgs],
            [m.value["n_examples"] for m in msgs])


# ---- phase a --------------------------------------------------------------
def train(cfg, n_parties: int, rounds: int, *, n_sequences: int,
          seed: int = 0):
    """Real federated training through ``Platform().train``; returns the
    TrainingResult and the host-clock end of each round."""
    from repro.api import Platform
    from repro.core.jobspec import FLJobSpec, PartySpec
    from repro.models import model as M

    spec = FLJobSpec(
        job_id=JOB_ID, model_arch=cfg.name,
        model_bytes=M.n_params(cfg) * 4,
        aggregation_algorithm="fedprox", prox_mu=0.001,
        rounds=rounds, lr=0.05, batch_size=8,
        parties={f"p{i}": PartySpec(f"p{i}") for i in range(n_parties)},
    )
    result = Platform().train(cfg, spec, n_sequences=n_sequences,
                              heterogeneous=True, eval_sequences=16,
                              seed=seed)
    fused = result.runtime.queue.topic(f"fused/{JOB_ID}").poll("chip-smoke")
    return result, [m.timestamp for m in fused]


def check_rounds(result) -> dict:
    """Phase a: each round's fold against the jax.numpy reference."""
    from repro.fl.aggregator import AggregationExecutor

    rt = result.runtime
    alg = rt.agg.alg
    published = rt.queue.topic(f"fused/{JOB_ID}").poll("chip-smoke")
    out = {"ok": True, "folds": []}
    errs, tols = [], []
    for rec, pub in zip(result.records, published):
        r = rec.round_idx
        ex = AggregationExecutor(JOB_ID, alg, rt.queue,
                                 group=f"chip-smoke-fold-{r}")
        n = ex.drain(r)
        fold = ex.state.result()  # fp32, before apply's cast
        updates, n_ex = _updates(rt, r)
        ref = reference_mean(updates, [alg.weight_of(k) for k in n_ex])
        err = _max_abs(fold, ref)
        tol = FP32_RTOL * max(1.0, _max_mag(ref))
        same = _bitwise_equal(alg.apply(pub.value, fold), pub.value)
        out["ok"] &= (n == len(updates) == rt.spec.n_parties
                      and err <= tol and same
                      and bool(np.isfinite(rec.global_loss)))
        errs.append(err)
        tols.append(tol)
        out["folds"].append(fold)
    out["ok"] &= len(out["folds"]) == len(result.records) > 0
    out["max_err"], out["tol"] = float(np.max(errs)), float(np.max(tols))
    return out


# ---- phase b --------------------------------------------------------------
def check_resume(result, uninterrupted) -> dict:
    """Phase b: drain half of round 0, checkpoint, resume in a new executor,
    drain the rest; the fold must equal the uninterrupted one exactly."""
    from repro.fl.aggregator import AggregationExecutor

    rt = result.runtime
    first = AggregationExecutor(JOB_ID, rt.agg.alg, rt.queue,
                                group="chip-smoke-resume")
    n1 = first.drain(0, max_messages=max(1, rt.spec.n_parties // 2))
    first.checkpoint()
    second = AggregationExecutor(JOB_ID, rt.agg.alg, rt.queue,
                                 group="chip-smoke-resume")
    resumed = second.resume()
    n2 = second.drain(0)
    fold = second.state.result()
    err = _max_abs(fold, uninterrupted)
    return {"ok": resumed and n1 + n2 == rt.spec.n_parties
            and second.state.n_fused == rt.spec.n_parties and err == 0.0,
            "drained": (n1, n2), "max_err": err}


# ---- phase c --------------------------------------------------------------
def check_drains(result, k: int = K_DRAIN) -> dict:
    """Phase c: batch and int8 drains of K retained updates vs the oracles."""
    from repro.kernels import fuse_quantized, quantize_update, ref

    alg = result.runtime.agg.alg
    updates, n_ex = _updates(result.runtime)
    updates, n_ex = updates[:k], n_ex[:k]
    total = sum(alg.weight_of(n) for n in n_ex)
    ws = [alg.weight_of(n) / total for n in n_ex]
    w = jnp.asarray(ws, jnp.float32)
    stack = lambda leaves: jnp.stack([x.reshape(-1) for x in leaves])

    batch = alg.fuse(updates, n_ex)
    want = [ref.fused_agg_ref(stack([jax.tree.leaves(u)[i] for u in updates]),
                              w) for i in range(len(jax.tree.leaves(batch)))]
    got = [x.reshape(-1) for x in jax.tree.leaves(batch)]
    batch_err, batch_ratio = _err_over_tol(got, want, BF16_RTOL)

    qs, ss = zip(*(quantize_update(u) for u in updates))
    quant = fuse_quantized(qs, ss, ws)
    want = [ref.quant_agg_ref(stack([jax.tree.leaves(x)[i] for x in qs]),
                              jnp.stack([jax.tree.leaves(x)[i] for x in ss])
                              * w)
            for i in range(len(jax.tree.leaves(quant)))]
    got = [x.reshape(-1) for x in jax.tree.leaves(quant)]
    int8_err, int8_ratio = _err_over_tol(got, want, INT8_RTOL)
    return {"ok": len(updates) == k and batch_ratio <= 1.0
            and int8_ratio <= 1.0,
            "k": len(updates), "batch_err": batch_err,
            "batch_ratio": batch_ratio, "int8_err": int8_err,
            "int8_ratio": int8_ratio}


# ---- phase d --------------------------------------------------------------
def kernel_programs(result) -> dict:
    """Phase d: compile each kernel at the largest leaf of an update; True
    where the program holds the Mosaic kernel (``tpu_custom_call``), False
    where it was interpreted."""
    from repro.kernels import interpret_mode
    from repro.kernels.fused_agg import fused_agg
    from repro.kernels.pair_fuse import pair_fuse
    from repro.kernels.quant_agg import quant_agg

    updates, _ = _updates(result.runtime)
    n = max(x.size for x in jax.tree.leaves(updates[0]))
    k = min(K_DRAIN, len(updates))
    mode = interpret_mode()
    vec = jax.ShapeDtypeStruct((n,), jnp.float32)
    progs = {
        "pair_fuse": (lambda a, b: pair_fuse(a, b, op="wsum", wa=1.0, wb=0.5,
                                             interpret=mode), (vec, vec)),
        "fused_agg": (lambda u, w: fused_agg(u, w, interpret=mode),
                      (jax.ShapeDtypeStruct((k, n), jnp.bfloat16),
                       jax.ShapeDtypeStruct((k,), jnp.float32))),
        "quant_agg": (lambda q, s: quant_agg(q, s, interpret=mode),
                      (jax.ShapeDtypeStruct((k, n), jnp.int8),
                       jax.ShapeDtypeStruct((k,), jnp.float32))),
    }
    return {name: "tpu_custom_call" in
            jax.jit(fn).lower(*args).compile().as_text()
            for name, (fn, args) in progs.items()}


# ---- driver ---------------------------------------------------------------
class CompileLog:
    """Backend compile (or persistent-cache load) durations, host clock."""

    def __init__(self):
        self.events = []  # (end time, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.time(), duration))

    def between(self, t0: float, t1: float):
        ds = [d for t, d in self.events if t0 <= t < t1]
        return len(ds), sum(ds)


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    from repro import compile_cache, configs

    cache_dir = compile_cache.enable()
    print(f"compile cache: {cache_dir}")
    log = CompileLog()
    cfg = configs.get_config("example-100m")
    n_parties, rounds = 4, 2
    ok = True

    t0 = time.time()
    result, round_end = train(cfg, n_parties, rounds, n_sequences=64)
    t_train = time.time() - t0
    for r, (start, end) in enumerate(zip([t0] + round_end, round_end)):
        n, s = log.between(start if r else 0.0, end)
        label = "set-up + round 0" if r == 0 else f"round {r}"
        print(f"{label}: wall {end - start:.3f} s, {n} compiles "
              f"{s:.3f} s, loss {result.records[r].global_loss:.4f}")

    t = time.time()
    a = check_rounds(result)
    ok &= a["ok"]
    print(f"phase a train+fuse: {'ok' if a['ok'] else 'FAILED'}  "
          f"train {t_train:.3f} s, check {time.time() - t:.3f} s, "
          f"fp32 max err {a['max_err']:.3e} (tol {a['tol']:.1e}), "
          f"published == cast(fold)")

    t = time.time()
    b = check_resume(result, a["folds"][0]) if a["folds"] else {
        "ok": False, "drained": (0, 0), "max_err": float("nan")}
    ok &= b["ok"]
    print(f"phase b resume: {'ok' if b['ok'] else 'FAILED'}  "
          f"{time.time() - t:.3f} s, drained {b['drained'][0]}+"
          f"{b['drained'][1]}, max diff to uninterrupted {b['max_err']:.1e}")

    t = time.time()
    c = check_drains(result)
    ok &= c["ok"]
    print(f"phase c drains: {'ok' if c['ok'] else 'FAILED'}  "
          f"{time.time() - t:.3f} s, K={c['k']}, batch(bf16) max err "
          f"{c['batch_err']:.3e} ({c['batch_ratio']:.3f} of tol), int8 max "
          f"err {c['int8_err']:.3e} ({c['int8_ratio']:.3f} of tol)")

    t = time.time()
    d = kernel_programs(result)
    ok &= all(d.values())
    print(f"phase d kernels: {'ok' if all(d.values()) else 'FAILED'}  "
          f"{time.time() - t:.3f} s, tpu_custom_call "
          + ", ".join(f"{k}={v}" for k, v in d.items()))

    n, s = log.between(0.0, time.time())
    stats = dev.memory_stats() or {}
    print(f"total: {time.time() - t0:.3f} s, {n} compiles {s:.3f} s, "
          f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    if not ok:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
