"""Mixture-of-Experts FFN: grouped gather/scatter dispatch (GShard-style
capacity, but without the quadratic one-hot dispatch einsum).

Routing is computed per sequence (group = batch element) so the gather /
scatter-add stay within the unsharded sequence axis: with batch sharded over
``data`` and experts over ``model`` the dispatch is communication-free and the
combine rides the normal tensor-parallel all-reduce.

Dispatch cost is O(tokens·E) for the rank bookkeeping plus pure-bandwidth
gathers — no FLOPs proportional to E·capacity·d_model (the classic GShard
dispatch einsum would be ~5x the model FLOPs at our shapes; see DESIGN.md).
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import mlp_specs, mlp_apply
from repro.models.spec import TensorSpec


def moe_specs(cfg: ModelConfig) -> Dict[str, TensorSpec]:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    s: Dict[str, TensorSpec] = {
        "router": TensorSpec((d, e), ("d_model", None), scale=0.5),
        "w_gate": TensorSpec((e, d, f), ("experts", "d_model", "d_ff")),
        "w_up": TensorSpec((e, d, f), ("experts", "d_model", "d_ff")),
        "w_down": TensorSpec((e, f, d), ("experts", "d_ff", "d_model")),
    }
    if cfg.num_shared_experts:
        s["shared"] = mlp_specs(d, f * cfg.num_shared_experts)
    return s


def capacity(cfg: ModelConfig, seq: int) -> int:
    c = math.ceil(seq * cfg.num_experts_per_tok / cfg.num_experts * cfg.capacity_factor)
    return max(4 * math.ceil(c / 4), 4)


def _route(cfg: ModelConfig, router: jax.Array, x: jax.Array):
    """Router probs + normalised top-k gates. x: (B,S,d)."""
    logits = jnp.einsum("bsd,de->bse", x, router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # (B,S,E)
    gate, idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)  # (B,S,k)
    gate = gate / jnp.clip(jnp.sum(gate, axis=-1, keepdims=True), 1e-9)
    return probs, gate, idx


def _dispatch_tables(gate, idx, e_rows: int, c: int, dtype):
    """Token/gate lookup tables (B, e_rows, C) from top-k assignments.

    rank = arrival order of each (token, k) within its expert; entries past
    capacity are dropped (gate 0)."""
    b, s, k = idx.shape
    onehot = jax.nn.one_hot(idx, e_rows, dtype=jnp.int32)  # (B,S,k,E)
    flat = onehot.reshape(b, s * k, e_rows)
    rank_flat = jnp.cumsum(flat, axis=1) - flat  # arrivals before me
    rank = jnp.take_along_axis(
        rank_flat.reshape(b, s, k, e_rows), idx[..., None], axis=-1
    )[..., 0]  # (B,S,k)
    keep = rank < c
    b_ix = jnp.arange(b, dtype=jnp.int32)[:, None, None]
    rank_c = jnp.where(keep, rank, c - 1).astype(jnp.int32)
    tok = jnp.broadcast_to(
        jnp.arange(s, dtype=jnp.int32)[None, :, None], (b, s, k)
    )
    table = jnp.zeros((b, e_rows, c), jnp.int32).at[
        b_ix, idx, rank_c
    ].max(jnp.where(keep, tok, 0), mode="drop")
    gate_table = jnp.zeros((b, e_rows, c), dtype).at[
        b_ix, idx, rank_c
    ].add(jnp.where(keep, gate, 0.0).astype(dtype), mode="drop")
    return table, gate_table


def _expert_ffn(xg, wg, wu, wd, gate_table):
    """(B,E,C,d) tokens through per-expert SwiGLU, gate-weighted."""
    g = jnp.einsum("becd,edf->becf", xg, wg)
    u = jnp.einsum("becd,edf->becf", xg, wu)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(xg.dtype) * u
    out = jnp.einsum("becf,efd->becd", h, wd)
    return out * gate_table[..., None]


def _gather_tokens(x, table):
    """x: (B,S,d); table: (B,E,C) -> (B,E,C,d) batched gather."""
    b, s, d = x.shape
    _, e, c = table.shape
    xg = jnp.take_along_axis(
        x[:, :, None, :], table.reshape(b, e * c, 1, 1), axis=1
    )
    return xg.reshape(b, e, c, d)


def _scatter_combine(x_like, table, out):
    b, s, d = x_like.shape
    _, e, c, _ = out.shape
    b_ix = jnp.arange(b, dtype=jnp.int32)[:, None]
    return jnp.zeros_like(x_like).at[
        b_ix, table.reshape(b, e * c)
    ].add(out.reshape(b, e * c, d), mode="drop")


def _aux_loss(probs, idx, e: int) -> jax.Array:
    frac = jnp.mean(
        jax.nn.one_hot(idx[..., 0], e, dtype=jnp.float32), axis=(0, 1)
    )  # fraction of tokens whose top-1 is e
    mean_prob = jnp.mean(probs, axis=(0, 1))
    return e * jnp.sum(frac * mean_prob)


def moe_apply(
    cfg: ModelConfig, p: Dict[str, jax.Array], x: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (y, aux_loss).

    Under the optimized profile with a mesh installed, dispatch runs inside
    shard_map (experts over the model axis, batch over data): routing,
    gather, expert FFN and combine are all device-local, and the combine
    rides one psum — GSPMD never sees the data-dependent gather/scatter
    (which it otherwise lowers to giant replicated all-reduces; see
    EXPERIMENTS.md §Perf H1)."""
    from repro.models.sharding_ctx import moe_shard_map_ctx

    ctx = moe_shard_map_ctx()
    if ctx is not None:
        return _moe_apply_shard_map(cfg, p, x, *ctx)

    b, s, d = x.shape
    e = cfg.num_experts
    c = capacity(cfg, s)
    probs, gate, idx = _route(cfg, p["router"], x)
    table, gate_table = _dispatch_tables(gate, idx, e, c, x.dtype)
    xg = _gather_tokens(x, table)
    out = _expert_ffn(xg, p["w_gate"], p["w_up"], p["w_down"], gate_table)
    y = _scatter_combine(x, table, out)
    if cfg.num_shared_experts:
        y = y + mlp_apply(p["shared"], x)
    return y, _aux_loss(probs, idx, e)


def _moe_apply_shard_map(cfg, p, x, mesh, batch_axes, model_axis):
    from jax.sharding import PartitionSpec as P

    e, k = cfg.num_experts, cfg.num_experts_per_tok
    m = dict(mesh.shape)[model_axis]  # works for Mesh and AbstractMesh
    e_pad = -(-e // m) * m
    el = e_pad // m
    c = capacity(cfg, x.shape[1])

    wg, wu, wd = p["w_gate"], p["w_up"], p["w_down"]
    if e_pad != e:  # pad experts so the model axis divides them
        padw = ((0, e_pad - e), (0, 0), (0, 0))
        wg, wu, wd = (jnp.pad(w, padw) for w in (wg, wu, wd))

    shared = p.get("shared")
    has_shared = shared is not None

    def local_fn(x_l, router, wg_l, wu_l, wd_l, *shared_ws):
        # routing over the FULL expert set, identical on every model shard
        probs, gate, idx = _route(cfg, router, x_l)
        table, gate_table = _dispatch_tables(gate, idx, e_pad, c, x_l.dtype)
        # slice this shard's experts from the dispatch tables
        j = jax.lax.axis_index(model_axis)
        table_l = jax.lax.dynamic_slice_in_dim(table, j * el, el, axis=1)
        gate_l = jax.lax.dynamic_slice_in_dim(gate_table, j * el, el, axis=1)
        xg = _gather_tokens(x_l, table_l)  # (B_l, el, C, d) — local
        out = _expert_ffn(xg, wg_l, wu_l, wd_l, gate_l)
        y = _scatter_combine(x_l, table_l, out)
        if has_shared:
            sg, su, sd = shared_ws
            y = y + mlp_apply({"w_gate": sg, "w_up": su, "w_down": sd}, x_l)
        if cfg.sequence_parallel:
            # combine + reshard in one collective: the residual stream is
            # sequence-sharded over the model axis, so reduce-scatter the
            # combined output back onto it (half the bytes of a full psum)
            y = jax.lax.psum_scatter(y, model_axis, scatter_dimension=1,
                                     tiled=True)
        else:
            y = jax.lax.psum(y, model_axis)
        # per-data-shard load-balance loss, averaged across shards (the
        # standard GShard/Switch practice; differs from the global-batch
        # aux by O(cross-shard covariance))
        aux = _aux_loss(probs, idx, e)
        if batch_axes:
            aux = jax.lax.pmean(aux, batch_axes)
        return y, aux

    # anchor x replicated-over-model in bf16 BEFORE the shard_map boundary
    # (otherwise GSPMD fuses an fp32 convert into the seq all-gather)
    from repro.models.sharding_ctx import constrain as _constrain

    x = _constrain(x, ("batch", None, None))

    bspec = P(batch_axes if batch_axes else None, None, None)
    out_y_spec = (
        P(batch_axes if batch_axes else None, model_axis, None)
        if cfg.sequence_parallel else bspec
    )
    in_specs = [
        bspec,  # x
        P(None, None),  # router (replicated)
        P(model_axis, None, None),  # w_gate
        P(model_axis, None, None),  # w_up
        P(model_axis, None, None),  # w_down
    ]
    args = [x, p["router"], wg, wu, wd]
    if has_shared:
        in_specs += [P(None, model_axis), P(None, model_axis),
                     P(model_axis, None)]
        args += [shared["w_gate"], shared["w_up"], shared["w_down"]]
    out_specs = (out_y_spec, P())
    fn = jax.shard_map(
        local_fn, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=out_specs, check_vma=False,
    )
    return fn(*args)
