"""Grouped-query attention: training, prefill, and decode-with-cache paths.

Features per assigned archs: GQA (any n_heads/n_kv_heads ratio), qk-norm
(Qwen3), rotary embeddings, causal masking, sliding-window masking (the
explicitly-flagged long-context variant for full-attention archs), and
cross-attention (Whisper decoder).

The jnp paths here are the reference; kernels/flash_attention provides the
Pallas TPU kernel for the same math (tests assert allclose).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .layers import apply_rotary, init_dense, rms_norm, rotary_cos_sin

__all__ = ["KVCache", "init_attention", "attention_train", "attention_prefill",
           "attention_decode", "cross_attention"]


class KVCache(NamedTuple):
    k: jax.Array  # [B, S, n_kv, hd]
    v: jax.Array  # [B, S, n_kv, hd]


def init_attention(key, cfg, dtype, cross: bool = False):
    d, hd = cfg.d_model, cfg.head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": init_dense(kq, d, cfg.n_heads * hd, dtype)["w"],
        "wk": init_dense(kk, d, cfg.n_kv_heads * hd, dtype)["w"],
        "wv": init_dense(kv, d, cfg.n_kv_heads * hd, dtype)["w"],
        "wo": init_dense(ko, cfg.n_heads * hd, d, dtype)["w"],
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


def _qkv(params, cfg, x, positions, rope: bool = True):
    hd = cfg.head_dim
    q = _split_heads(x @ params["wq"], cfg.n_heads, hd)
    k = _split_heads(x @ params["wk"], cfg.n_kv_heads, hd)
    v = _split_heads(x @ params["wv"], cfg.n_kv_heads, hd)
    if "q_norm" in params:
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    if rope:
        cos, sin = rotary_cos_sin(positions, hd, cfg.rope_theta, jnp.float32)
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)
    return q, k, v


import os as _os

# GQA layout strategy.  "expand" (default) broadcasts K/V to the full H
# query heads so the head axis keeps its model-axis sharding end to end —
# the (hkv, group) reshape of the "grouped" variant splits a sharded axis
# and forces XLA to all-gather Q inside the attention loop (~120 GB/device
# on qwen3 train_4k; see EXPERIMENTS.md §Perf iteration 1).  The env toggle
# reproduces the pre-fix baseline for the perf log.
_GQA_GROUPED = _os.environ.get("REPRO_FLASH_GQA_GROUPED", "0") == "1"


def _expand_kv(k, group: int):
    if group == 1:
        return k
    b, s, hkv, hd = k.shape
    return jnp.repeat(k, group, axis=2)


def _sdpa_small(q, k, v, mask, scale):
    """Materialized-logits attention for short sequences / decode.

    q: [B,Sq,H,hd]; k/v: [B,Sk,Hkv,hd]; GQA via head grouping.
    mask: [B,Sq,Sk] or None."""
    b, sq, h, hd = q.shape
    hkv = k.shape[2]
    group = h // hkv
    if not _GQA_GROUPED:
        k = _expand_kv(k, group)
        v = _expand_kv(v, group)
        logits = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
        ) * scale
        if mask is not None:
            logits = jnp.where(mask[:, None, :, :], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        return out.reshape(b, sq, h * hd)
    q = q.reshape(b, sq, hkv, group, hd)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", q, k).astype(jnp.float32) * scale
    if mask is not None:
        logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(b, sq, h * hd)


def _flash_sdpa(q, k, v, scale, window: Optional[int], q_block: int = 512,
                kv_block: int = 1024):
    """Online-softmax chunked causal attention (the pure-JAX flash path).

    Never materializes more than one [*, q_block, kv_block] logits tile per
    (double) scan step, so 32k-token prefill fits HBM.  This is also the
    oracle for kernels/flash_attention (same math, same blocking)."""
    b, s, h, hd = q.shape
    hkv = k.shape[2]
    group = h // hkv
    if not _GQA_GROUPED:
        # head-sharding-preserving layout: expand K/V to H heads
        k = _expand_kv(k, group)
        v = _expand_kv(v, group)
        hkv, group = h, 1
    nq = s // q_block
    nk = s // kv_block
    qb = q.reshape(b, nq, q_block, hkv, group, hd)
    kb = k.reshape(b, nk, kv_block, hkv, hd)
    vb = v.reshape(b, nk, kv_block, hkv, hd)

    q_idx = jnp.arange(q_block)
    k_idx = jnp.arange(kv_block)

    def q_step(_, qi):
        qtile = qb[:, qi]  # [B, qblk, hkv, g, hd]

        def kv_step(carry, ki):
            m_run, l_run, acc = carry
            ktile = kb[:, ki]
            vtile = vb[:, ki]
            logits = (
                jnp.einsum(
                    "bqhgd,bkhd->bhgqk", qtile, ktile,
                    preferred_element_type=jnp.float32,
                )
                * scale
            )
            qpos = qi * q_block + q_idx[:, None]
            kpos = ki * kv_block + k_idx[None, :]
            mask = kpos <= qpos
            if window is not None:
                mask &= kpos > qpos - window
            logits = jnp.where(mask[None, None, None], logits, -1e30)
            m_new = jnp.maximum(m_run, logits.max(axis=-1))
            alpha = jnp.exp(m_run - m_new)
            p = jnp.exp(logits - m_new[..., None])
            l_new = l_run * alpha + p.sum(axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(vtile.dtype), vtile,
                preferred_element_type=jnp.float32,
            )
            return (m_new, l_new, acc), None

        m0 = jnp.full((b, hkv, group, q_block), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, hkv, group, q_block), jnp.float32)
        a0 = jnp.zeros((b, hkv, group, q_block, hd), jnp.float32)
        (m_f, l_f, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l_f, 1e-30)[..., None]
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_step, None, jnp.arange(nq))
    # outs: [nq, B, hkv, g, q_block, hd] -> [B, nq, q_block, hkv, g, hd]
    out = jnp.moveaxis(outs, 0, 1).transpose(0, 1, 4, 2, 3, 5)
    out = out.reshape(b, nq, q_block, h, hd).reshape(b, s, h * hd)
    return out


# Sequences at or below this use materialized-logits attention.
_SMALL_SEQ = 1024


def _causal_mask(sq: int, sk: int, window: Optional[int], q_offset=0):
    """[sq, sk] True = attend.  q position i attends k position j iff
    j <= i+q_offset and (no window or j > i+q_offset-window)."""
    qi = jnp.arange(sq)[:, None] + q_offset
    kj = jnp.arange(sk)[None, :]
    m = kj <= qi
    if window is not None:
        m &= kj > qi - window
    return m


def _causal_attention(q, k, v, scale, window, batch):
    s = q.shape[1]
    if s <= _SMALL_SEQ:
        mask = jnp.broadcast_to(_causal_mask(s, s, window)[None], (batch, s, s))
        return _sdpa_small(q, k, v, mask, scale)
    qb = 512 if s % 512 == 0 else _largest_divisor_block(s)
    kb = 1024 if s % 1024 == 0 else qb
    return _flash_sdpa(q, k, v, scale, window, q_block=qb, kv_block=kb)


def _largest_divisor_block(s: int, cap: int = 512) -> int:
    for b in range(min(cap, s), 0, -1):
        if s % b == 0:
            return b
    return 1


def attention_train(params, cfg, x, window: Optional[int] = None):
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    q, k, v = _qkv(params, cfg, x, positions)
    out = _causal_attention(q, k, v, cfg.head_dim ** -0.5, window, b)
    return out @ params["wo"]


def attention_prefill(params, cfg, x, window: Optional[int] = None):
    """Returns (output, KVCache) for subsequent decode."""
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    q, k, v = _qkv(params, cfg, x, positions)
    out = _causal_attention(q, k, v, cfg.head_dim ** -0.5, window, b)
    return out @ params["wo"], KVCache(k=k, v=v)


def attention_decode(params, cfg, x, cache: KVCache, cache_len,
                     window: Optional[int] = None):
    """One-token decode: x [B,1,D]; cache holds S_max past positions.

    ``cache_len`` [B] int32 — number of valid positions.  The new token is
    written at index cache_len (static-shape dynamic_update_slice per row).
    """
    b, one, _ = x.shape
    assert one == 1
    s_max = cache.k.shape[1]
    positions = cache_len[:, None]  # [B,1]
    q, k_new, v_new = _qkv(params, cfg, x, positions)

    # masked (pointwise) write: a per-row dynamic_update_slice lowers to a
    # scatter that XLA SPMD can only partition by replicating the whole
    # cache ("involuntary full rematerialization"); the broadcast-compare
    # select keeps the [B, S, kv, hd] buffer fully sharded.
    with jax.named_scope("kv_update"):
        write_mask = (
            jnp.arange(s_max)[None, :]
            == jnp.clip(cache_len, 0, s_max - 1)[:, None]
        )[:, :, None, None]  # [B, S, 1, 1]

        def write(buf, new):
            return jnp.where(write_mask, new.astype(buf.dtype), buf)

        k = write(cache.k, k_new)
        v = write(cache.v, v_new)

    kj = jnp.arange(s_max)[None, :]  # [1, S]
    valid = kj <= cache_len[:, None]  # include the just-written slot
    if window is not None:
        valid &= kj > cache_len[:, None] - window
    out = _sdpa_decode(q, k, v, valid, cfg.head_dim ** -0.5)
    return out @ params["wo"], KVCache(k=k, v=v)


def _sdpa_decode(q, k, v, valid, scale):
    """Attention of one query token per row, reading each KV head once:
    q [B,1,H,hd] is split into [B, kv, group, hd] against the unexpanded
    [B, S, kv, hd] cache, so no copy of K/V per query head is made.  The
    cache is not reshaped, so a sequence-sharded cache stays sharded.
    Logits and output accumulate in f32; ``valid`` [B, S] masks the cache."""
    b, _, h, hd = q.shape
    hkv = k.shape[2]
    qg = q.reshape(b, hkv, h // hkv, hd)
    logits = jnp.einsum("bhgd,bkhd->bhgk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgk,bkhd->bhgd", probs, v,
                     preferred_element_type=jnp.float32)
    return out.astype(v.dtype).reshape(b, 1, h * hd)


def cross_attention(params, cfg, x, enc_kv: KVCache):
    """Decoder cross-attention to fixed encoder states (no rope, no mask)."""
    b, s, _ = x.shape
    positions = jnp.zeros((b, s), jnp.int32)
    q = _split_heads(x @ params["wq"], cfg.n_heads, cfg.head_dim)
    out = _sdpa_small(q, enc_kv.k, enc_kv.v, None, cfg.head_dim ** -0.5)
    return out @ params["wo"]


def encode_kv(params, cfg, enc_out):
    """Precompute cross-attention K/V from encoder output."""
    k = _split_heads(enc_out @ params["wk"], cfg.n_kv_heads, cfg.head_dim)
    v = _split_heads(enc_out @ params["wv"], cfg.n_kv_heads, cfg.head_dim)
    return KVCache(k=k, v=v)
