"""Unified model: pattern-scanned decoder (+ optional encoder / frontends).

Layer layout: ``cfg.pattern`` (one period) × ``cfg.n_repeats``, executed as
``lax.scan`` over repeats with per-position stacked parameters — 40-layer
models lower to one-period HLO bodies, keeping the 80 dry-run compiles
tractable (DESIGN.md §5).

Entry points:
  init_params / init_caches
  forward_train(params, tokens, extra_embeds)        -> (logits_fn-free loss pieces)
  prefill(params, tokens, caches, extra_embeds)      -> (last_logits, caches, aux)
  decode_step(params, token, caches, cache_len)      -> (logits, caches)
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from . import attention as attn
from .blocks import (
    block_decode,
    block_prefill,
    block_train,
    init_block,
    init_block_cache,
)
from .config import ModelConfig
from .layers import apply_norm, init_embedding, init_norm

__all__ = ["Model"]


def _stack(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


class Model:
    def __init__(self, cfg: ModelConfig, act_shard=None):
        """``act_shard(x, kind)`` is an optional activation-sharding hook
        (launch/steps.py passes sequence-parallel constraints; tests and
        single-device runs leave it None)."""
        self.cfg = cfg
        self.dtype = jnp.dtype(cfg.dtype)
        self.act_shard = act_shard or (lambda x, kind: x)
        self.remat = True  # launch/steps may override

    # ------------------------------------------------------------------ init

    def init_params(self, key) -> dict[str, Any]:
        cfg = self.cfg
        keys = jax.random.split(key, 8)
        p: dict[str, Any] = {
            "embed": init_embedding(keys[0], cfg.vocab, cfg.d_model, self.dtype),
            "final_norm": init_norm(cfg.norm, cfg.d_model, self.dtype),
        }
        if not cfg.tie_embeddings:
            p["lm_head"] = init_embedding(keys[1], cfg.vocab, cfg.d_model, self.dtype)
        # decoder pattern: stacked over repeats per pattern position
        layer_keys = jax.random.split(keys[2], cfg.n_repeats * len(cfg.pattern))
        layers = []
        cross = cfg.is_encoder_decoder
        for pos, spec in enumerate(cfg.pattern):
            per_repeat = [
                init_block(
                    layer_keys[r * len(cfg.pattern) + pos], cfg, spec, self.dtype,
                    cross=cross,
                )
                for r in range(cfg.n_repeats)
            ]
            layers.append(_stack(per_repeat))
        p["layers"] = tuple(layers)
        if cfg.is_encoder_decoder:
            from .config import LayerSpec

            enc_spec = LayerSpec(mixer="attn", ffn="mlp")
            enc_keys = jax.random.split(keys[3], cfg.n_enc_layers)
            p["encoder"] = {
                "layers": _stack(
                    [
                        init_block(k, cfg, enc_spec, self.dtype)
                        for k in enc_keys
                    ]
                ),
                "final_norm": init_norm(cfg.norm, cfg.d_model, self.dtype),
            }
        return p

    def init_caches(self, batch: int, max_len: int):
        cfg = self.cfg
        caches = []
        cross_ctx = cfg.enc_ctx if cfg.is_encoder_decoder else 0
        for spec in cfg.pattern:
            one = init_block_cache(cfg, spec, batch, max_len, self.dtype, cross_ctx)
            stacked = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (cfg.n_repeats,) + x.shape), one
            )
            caches.append(stacked)
        return tuple(caches)

    # ----------------------------------------------------------------- embed

    def _embed(self, params, tokens, extra_embeds=None):
        x = params["embed"]["w"][tokens]
        if extra_embeds is not None:
            # stub modality frontend: precomputed patch/frame embeddings
            x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
        return x

    def _logits(self, params, x):
        head = params.get("lm_head", params["embed"])["w"]
        with jax.named_scope("lm_head"):
            return x @ head.T

    # --------------------------------------------------------------- encoder

    def _encode(self, params, enc_embeds):
        """Whisper-style encoder over stub frame embeddings (non-causal)."""
        cfg = self.cfg
        from .config import LayerSpec

        enc_spec = LayerSpec(mixer="attn", ffn="mlp")

        def step(x, layer_p):
            h = apply_norm(layer_p["norm1"], x, cfg.norm)
            b, s, _ = h.shape
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            q, k, v = attn._qkv(layer_p["mixer"], cfg, h, positions, rope=False)
            y = attn._sdpa_small(q, k, v, None, cfg.head_dim ** -0.5)
            x = x + y @ layer_p["mixer"]["wo"]
            from .blocks import _ffn_apply

            x, _ = _ffn_apply(layer_p, cfg, enc_spec, x)
            return x, None

        x, _ = jax.lax.scan(step, enc_embeds.astype(self.dtype),
                            params["encoder"]["layers"])
        return apply_norm(params["encoder"]["final_norm"], x, cfg.norm)

    # ----------------------------------------------------------------- train

    def forward_train(self, params, tokens, extra_embeds=None, enc_embeds=None):
        """Full causal forward; returns (hidden, aux_loss)."""
        cfg = self.cfg
        enc_out = self._encode(params, enc_embeds) if enc_embeds is not None else None
        x = self._embed(params, tokens, extra_embeds)

        def repeat_step(carry, layer_slices):
            x, aux = carry
            x = self.act_shard(x, "residual")
            for pos, spec in enumerate(cfg.pattern):
                x, a = block_train(
                    layer_slices[pos], cfg, spec, x,
                    window=cfg.sliding_window, enc_out=enc_out,
                )
                aux = aux + a
            return (x, aux), None

        # remat: recompute the layer body in backward — bounds activation
        # memory to one period per repeat (hillclimb knob: see EXPERIMENTS.md)
        body = jax.checkpoint(repeat_step) if self.remat else repeat_step
        (x, aux), _ = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), params["layers"]
        )
        x = apply_norm(params["final_norm"], x, cfg.norm)
        return x, aux

    def loss(self, params, tokens, labels, extra_embeds=None, enc_embeds=None,
             chunk: int = 256):
        """Chunked softmax cross-entropy (never materializes [B,S,V])."""
        x, aux = self.forward_train(params, tokens, extra_embeds, enc_embeds)
        if extra_embeds is not None:
            x = x[:, extra_embeds.shape[1]:]  # loss over text positions only
        head = params.get("lm_head", params["embed"])["w"]
        b, s, d = x.shape
        if s % chunk != 0:
            chunk = s
        n = s // chunk
        xs = x.reshape(b, n, chunk, d)
        ls = labels.reshape(b, n, chunk)

        @jax.checkpoint
        def chunk_loss_inner(xc, lc):
            logits = (xc @ head.T).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
            return (lse - gold).sum()

        def chunk_loss(_, inp):
            xc, lc = inp  # [B, chunk, D], [B, chunk]
            return None, chunk_loss_inner(xc, lc)

        _, losses = jax.lax.scan(
            chunk_loss, None, (jnp.moveaxis(xs, 1, 0), jnp.moveaxis(ls, 1, 0))
        )
        return losses.sum() / (b * s) + aux

    # --------------------------------------------------------------- serving

    def prefill(self, params, tokens, caches, extra_embeds=None, enc_embeds=None):
        cfg = self.cfg
        enc_out = self._encode(params, enc_embeds) if enc_embeds is not None else None
        x = self._embed(params, tokens, extra_embeds)

        def repeat_step(carry, slices):
            x, aux = carry
            layer_slices, cache_slices = slices
            new_caches = []
            x = self.act_shard(x, "residual")
            for pos, spec in enumerate(cfg.pattern):
                x, nc, a = block_prefill(
                    layer_slices[pos], cfg, spec, x, cache_slices[pos],
                    window=cfg.sliding_window, enc_out=enc_out,
                )
                new_caches.append(nc)
                aux = aux + a
            return (x, aux), tuple(new_caches)

        (x, aux), new_caches = jax.lax.scan(
            repeat_step, (x, jnp.zeros((), jnp.float32)), (params["layers"], caches)
        )
        x = apply_norm(params["final_norm"], x, cfg.norm)
        logits = self._logits(params, x[:, -1:])
        return logits, new_caches, aux

    def decode_step(self, params, token, caches, cache_len):
        """token: [B, 1] int32; cache_len: [B] valid entries per row."""
        cfg = self.cfg
        x = self._embed(params, token)

        def repeat_step(carry, slices):
            x = carry
            layer_slices, cache_slices = slices
            new_caches = []
            for pos, spec in enumerate(cfg.pattern):
                x, nc, _ = block_decode(
                    layer_slices[pos], cfg, spec, x, cache_slices[pos],
                    cache_len, window=cfg.sliding_window,
                )
                new_caches.append(nc)
            return x, tuple(new_caches)

        x, new_caches = jax.lax.scan(repeat_step, x, (params["layers"], caches))
        x = apply_norm(params["final_norm"], x, cfg.norm)
        logits = self._logits(params, x)
        return logits, new_caches
