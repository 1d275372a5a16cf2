"""Decoder-block assembly: (norm → mixer → residual) → [cross-attn] →
(norm → ffn → residual), generic over mixer/ffn kinds and execution phase.

Caches are per-pattern-position pytrees; for scanned repeats every leaf
carries a leading n_repeats axis (handled by model.py's scans).
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import attention as attn
from . import mamba as mb
from . import xlstm as xl
from .config import LayerSpec, ModelConfig
from .layers import apply_norm, init_mlp, init_norm, mlp
from .moe import init_moe, moe_ffn

__all__ = ["init_block", "init_block_cache", "block_train", "block_prefill",
           "block_decode"]


def init_block(key, cfg: ModelConfig, spec: LayerSpec, dtype, cross: bool = False):
    keys = jax.random.split(key, 5)
    p: dict[str, Any] = {"norm1": init_norm(cfg.norm, cfg.d_model, dtype)}
    if spec.mixer == "attn":
        p["mixer"] = attn.init_attention(keys[0], cfg, dtype)
    elif spec.mixer == "mamba":
        p["mixer"] = mb.init_mamba(keys[0], cfg, dtype)
    elif spec.mixer == "mlstm":
        p["mixer"] = xl.init_mlstm(keys[0], cfg, dtype)
    else:
        p["mixer"] = xl.init_slstm(keys[0], cfg, dtype)
    if cross:
        p["norm_cross"] = init_norm(cfg.norm, cfg.d_model, dtype)
        p["cross"] = attn.init_attention(keys[1], cfg, dtype, cross=True)
    if spec.ffn != "none":
        p["norm2"] = init_norm(cfg.norm, cfg.d_model, dtype)
        if spec.ffn == "mlp":
            p["ffn"] = init_mlp(keys[2], cfg.d_model, cfg.d_ff, dtype)
        else:
            p["ffn"] = init_moe(keys[2], cfg, dtype)
    return p


def init_block_cache(cfg: ModelConfig, spec: LayerSpec, batch: int,
                     max_len: int, dtype, cross_ctx: int = 0):
    """Zero-initialized per-layer cache for decode."""
    cache: dict[str, Any] = {}
    if spec.mixer == "attn":
        kvshape = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
        cache["kv"] = attn.KVCache(
            k=jnp.zeros(kvshape, dtype), v=jnp.zeros(kvshape, dtype)
        )
    elif spec.mixer == "mamba":
        cache["ssm"] = mb.init_mamba_state(cfg, batch)
    elif spec.mixer == "mlstm":
        cache["xl"] = xl.init_mlstm_state(cfg, batch)
    else:
        cache["xl"] = xl.init_slstm_state(cfg, batch)
    if cross_ctx:
        kvshape = (batch, cross_ctx, cfg.n_kv_heads, cfg.head_dim)
        cache["cross_kv"] = attn.KVCache(
            k=jnp.zeros(kvshape, dtype), v=jnp.zeros(kvshape, dtype)
        )
    return cache


def _ffn_apply(p, cfg, spec: LayerSpec, x):
    if spec.ffn == "none":
        return x, 0.0
    h = apply_norm(p["norm2"], x, cfg.norm)
    if spec.ffn == "mlp":
        with jax.named_scope("mlp"):
            return x + mlp(p["ffn"], h), 0.0
    with jax.named_scope("moe"):
        y, aux = moe_ffn(p["ffn"], cfg, h)
    return x + y, aux


def block_train(p, cfg, spec: LayerSpec, x, window=None, enc_out=None):
    h = apply_norm(p["norm1"], x, cfg.norm)
    if spec.mixer == "attn":
        with jax.named_scope("attention"):
            x = x + attn.attention_train(p["mixer"], cfg, h, window)
    elif spec.mixer == "mamba":
        x = x + mb.mamba_train(p["mixer"], cfg, h)
    elif spec.mixer == "mlstm":
        x = x + xl.mlstm_train(p["mixer"], cfg, h)
    else:
        x = x + xl.slstm_train(p["mixer"], cfg, h)
    if "cross" in p and enc_out is not None:
        hc = apply_norm(p["norm_cross"], x, cfg.norm)
        enc_kv = attn.encode_kv(p["cross"], cfg, enc_out)
        x = x + attn.cross_attention(p["cross"], cfg, hc, enc_kv)
    return _ffn_apply(p, cfg, spec, x)


def block_prefill(p, cfg, spec: LayerSpec, x, cache, window=None, enc_out=None):
    """Runs the block over the prompt and fills the cache in-place-style."""
    h = apply_norm(p["norm1"], x, cfg.norm)
    new_cache = dict(cache)
    if spec.mixer == "attn":
        with jax.named_scope("attention"):
            y, kv = attn.attention_prefill(p["mixer"], cfg, h, window)
        x = x + y
        # write prompt K/V into the fixed-size buffer
        buf = cache["kv"]
        with jax.named_scope("kv_update"):
            new_cache["kv"] = attn.KVCache(
                k=jax.lax.dynamic_update_slice(buf.k, kv.k.astype(buf.k.dtype), (0, 0, 0, 0)),
                v=jax.lax.dynamic_update_slice(buf.v, kv.v.astype(buf.v.dtype), (0, 0, 0, 0)),
            )
    elif spec.mixer == "mamba":
        # run the train path and separately compute the final state
        y, state = _mamba_prefill(p["mixer"], cfg, h)
        x = x + y
        new_cache["ssm"] = state
    elif spec.mixer == "mlstm":
        y, state = _xlstm_prefill(p["mixer"], cfg, h, kind="mlstm")
        x = x + y
        new_cache["xl"] = state
    else:
        y, state = _xlstm_prefill(p["mixer"], cfg, h, kind="slstm")
        x = x + y
        new_cache["xl"] = state
    if "cross" in p and enc_out is not None:
        hc = apply_norm(p["norm_cross"], x, cfg.norm)
        enc_kv = attn.encode_kv(p["cross"], cfg, enc_out)
        x = x + attn.cross_attention(p["cross"], cfg, hc, enc_kv)
        new_cache["cross_kv"] = enc_kv
    x, aux = _ffn_apply(p, cfg, spec, x)
    return x, new_cache, aux


def block_decode(p, cfg, spec: LayerSpec, x, cache, cache_len, window=None):
    h = apply_norm(p["norm1"], x, cfg.norm)
    new_cache = dict(cache)
    if spec.mixer == "attn":
        with jax.named_scope("attention"):
            y, kv = attn.attention_decode(p["mixer"], cfg, h, cache["kv"], cache_len, window)
        x = x + y
        new_cache["kv"] = kv
    elif spec.mixer == "mamba":
        y, state = mb.mamba_decode(p["mixer"], cfg, h, cache["ssm"])
        x = x + y
        new_cache["ssm"] = state
    elif spec.mixer == "mlstm":
        y, state = xl.mlstm_decode(p["mixer"], cfg, h, cache["xl"])
        x = x + y
        new_cache["xl"] = state
    else:
        y, state = xl.slstm_decode(p["mixer"], cfg, h, cache["xl"])
        x = x + y
        new_cache["xl"] = state
    if "cross" in p and "cross_kv" in cache:
        hc = apply_norm(p["norm_cross"], x, cfg.norm)
        x = x + attn.cross_attention(p["cross"], cfg, hc, cache["cross_kv"])
    x, aux = _ffn_apply(p, cfg, spec, x)
    return x, new_cache, aux


def _mamba_prefill(params, cfg, x):
    """Mamba over the prompt, returning output + final recurrent state."""
    import jax.numpy as jnp

    xi = x @ params["in_proj"]
    xz, z = jnp.split(xi, 2, axis=-1)
    xc = mb._causal_conv(params, xz)
    y, h_final = mb.ssm_scan_chunked(params, xc)
    out = (y.astype(x.dtype) * jax.nn.silu(z)) @ params["out_proj"]
    dc = cfg.mamba_d_conv
    conv_tail = xz[:, -(dc - 1):, :].astype(xz.dtype)
    return out, mb.MambaState(conv=conv_tail, ssm=h_final)


def _xlstm_prefill(params, cfg, x, kind: str):
    """xLSTM over the prompt: final state comes out of the chunked scan."""
    if kind == "mlstm":
        return xl._mlstm_scan(params, cfg, x)
    return xl._slstm_scan(params, cfg, x)
