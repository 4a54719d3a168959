"""GQA attention: full/causal/local/cross, chunked online computation, KV cache.

Memory discipline: full-sequence attention is computed with a lax.scan over
query chunks so the (Sq, Sk) score matrix is never fully materialised —
peak transient is (B, KV, G, q_chunk, Sk) in fp32. GQA is computed with a
grouped einsum (no head replication of K/V).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import rms_norm, rms_norm_spec, rope
from repro.models.sharding_ctx import constrain, opt_feature
from repro.models.spec import TensorSpec

Cache = Dict[str, jax.Array]

NEG_INF = -1e30


# --------------------------------------------------------------------------
# specs
# --------------------------------------------------------------------------
def attn_specs(cfg: ModelConfig, cross: bool = False) -> Dict[str, TensorSpec]:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    s: Dict[str, TensorSpec] = {
        "wq": TensorSpec((d, h, hd), ("d_model", "heads", None), fan_in=d),
        "wk": TensorSpec((d, kv, hd), ("d_model", "kv_heads", None),
                         fan_in=d),
        "wv": TensorSpec((d, kv, hd), ("d_model", "kv_heads", None),
                         fan_in=d),
        "wo": TensorSpec((h, hd, d), ("heads", None, "d_model"),
                         fan_in=h * hd),
    }
    if cfg.qkv_bias:
        s["bq"] = TensorSpec((h, hd), ("heads", None), init="zeros")
        s["bk"] = TensorSpec((kv, hd), ("kv_heads", None), init="zeros")
        s["bv"] = TensorSpec((kv, hd), ("kv_heads", None), init="zeros")
    if cfg.qk_norm:
        s["q_norm"] = rms_norm_spec(hd)
        s["k_norm"] = rms_norm_spec(hd)
    return s


# --------------------------------------------------------------------------
# core grouped attention
# --------------------------------------------------------------------------
def _grouped_attn(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Sk, KV, D)
    v: jax.Array,  # (B, Sk, KV, D)
    mask: jax.Array,  # (Sq, Sk) or (B, Sq, Sk) bool; True = attend
) -> jax.Array:
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, d)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(d).astype(jnp.float32)
    if mask.ndim == 2:
        m = mask[None, None, None]
    else:
        m = mask[:, None, None]
    scores = jnp.where(m, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(b, sq, h, d)


def chunked_causal_attn(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_positions: jax.Array,  # (Sq,)
    k_positions: jax.Array,  # (Sk,)
    window: Optional[int] = None,
    q_chunk: int = 256,
) -> jax.Array:
    """Causal (optionally sliding-window) attention, scanned over q chunks."""
    b, sq, h, d = q.shape
    if sq <= q_chunk:
        mask = k_positions[None, :] <= q_positions[:, None]
        if window is not None:
            mask &= (q_positions[:, None] - k_positions[None, :]) < window
        return _grouped_attn(q, k, v, mask)

    n = sq // q_chunk
    assert n * q_chunk == sq, f"seq {sq} not divisible by q_chunk {q_chunk}"
    qs = q.reshape(b, n, q_chunk, h, d).transpose(1, 0, 2, 3, 4)
    ps = q_positions.reshape(n, q_chunk)

    def body(_, xs):
        qc, pc = xs
        mask = k_positions[None, :] <= pc[:, None]
        if window is not None:
            mask &= (pc[:, None] - k_positions[None, :]) < window
        return None, _grouped_attn(qc, k, v, mask)

    _, out = jax.lax.scan(body, None, (qs, ps))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, d)


# --------------------------------------------------------------------------
# block application (projections + rope + cache handling)
# --------------------------------------------------------------------------
def _project_qkv(cfg: ModelConfig, p, x):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def self_attention(
    cfg: ModelConfig,
    p: Dict[str, jax.Array],
    x: jax.Array,  # (B, S, d)
    positions: jax.Array,  # (S,)
    *,
    window: Optional[int] = None,
    cache: Optional[Cache] = None,
    t: Optional[jax.Array] = None,  # scalar current position (decode)
) -> Tuple[jax.Array, Optional[Cache]]:
    """Self attention. Training/prefill when cache is None or S>1 fills it;
    decode when S==1 reads+updates the ring-buffer cache."""
    q, k, v = _project_qkv(cfg, p, x)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    if opt_feature("kv_anchor") and x.shape[1] > 1:
        # §Perf H3: with sequence-parallel residuals GSPMD otherwise keeps
        # K/V sequence-sharded and emits an fp32 all-reduce of the attention
        # output PER q-chunk (hundreds per layer). Anchor K/V — GQA K/V are
        # small (kv_heads x head_dim) — to sequence-replicated bf16, so they
        # are all-gathered once per layer and every chunk-scan contraction
        # over the key axis is device-local. (Anchoring q as well was tried
        # and REFUTED: its backward resharding gathered full-width dq per
        # layer; see EXPERIMENTS.md §Perf H3.)
        k = constrain(k, ("batch", None, "kv_heads", None))
        v = constrain(v, ("batch", None, "kv_heads", None))

    if cache is None:
        out = chunked_causal_attn(q, k, v, positions, positions, window=window)
        new_cache = None
    elif x.shape[1] > 1:  # prefill into cache
        s = x.shape[1]
        cap = cache["k"].shape[1]
        out = chunked_causal_attn(q, k, v, positions, positions, window=window)
        if cap <= s:
            # windowed (lattn/SWA) caches keep only the last `cap` positions
            new_cache = {
                "k": k[:, s - cap:],
                "v": v[:, s - cap:],
                "pos": positions[s - cap:].astype(jnp.int32),
            }
        else:
            pad = cap - s
            new_cache = {
                "k": jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))),
                "v": jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0))),
                "pos": jnp.concatenate(
                    [positions.astype(jnp.int32),
                     jnp.full((pad,), -1, jnp.int32)]
                ),
            }
    else:  # single-token decode against ring buffer
        cap = cache["k"].shape[1]
        slot = (t % cap).astype(jnp.int32)
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
        cpos = jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], t[None].astype(jnp.int32), slot, axis=0
        )
        valid = (cpos >= 0) & (cpos <= t)
        if window is not None:
            valid &= (t - cpos) < window
        out = _grouped_attn(q, ck, cv, valid[None, :])
        new_cache = {"k": ck, "v": cv, "pos": cpos}

    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, new_cache


def cross_attention(
    cfg: ModelConfig,
    p: Dict[str, jax.Array],
    x: jax.Array,  # (B, S, d) text stream
    kv_embeds: Optional[jax.Array],  # (B, P, d) image/frame embeddings
    cache: Optional[Cache] = None,
) -> Tuple[jax.Array, Optional[Cache]]:
    """Cross attention over a fixed modality-token set (no causal mask).

    During prefill, K/V are projected from ``kv_embeds`` and cached; during
    decode they are read from the cache (O(P) per step)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    if cache is not None and kv_embeds is None:
        k, v = cache["k"], cache["v"]
        new_cache = cache
    else:
        k = jnp.einsum("bpd,dhk->bphk", kv_embeds, p["wk"])
        v = jnp.einsum("bpd,dhk->bphk", kv_embeds, p["wv"])
        if cfg.qk_norm:
            k = rms_norm(k, p["k_norm"], cfg.norm_eps)
        new_cache = {"k": k, "v": v} if cache is not None else None
    p_tokens = k.shape[1]
    mask = jnp.ones((1, p_tokens), dtype=bool)
    out = _grouped_attn(q, k, v, mask)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), new_cache


def attn_cache_specs(
    cfg: ModelConfig, batch: int, capacity: int
) -> Dict[str, TensorSpec]:
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": TensorSpec((batch, capacity, kv, hd), ("batch", "cache_seq", "kv_heads", None)),
        "v": TensorSpec((batch, capacity, kv, hd), ("batch", "cache_seq", "kv_heads", None)),
        "pos": TensorSpec((capacity,), ("cache_seq",), init="zeros", dtype="int32"),
    }


def xattn_cache_specs(cfg: ModelConfig, batch: int) -> Dict[str, TensorSpec]:
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    p = cfg.num_image_tokens
    return {
        "k": TensorSpec((batch, p, kv, hd), ("batch", None, "kv_heads", None)),
        "v": TensorSpec((batch, p, kv, hd), ("batch", None, "kv_heads", None)),
    }
