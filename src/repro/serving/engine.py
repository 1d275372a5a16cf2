"""Batched serving engine: prefill + decode loop with KV-cache management
and samplers, usable standalone or under the RT admission runtime.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as span

from repro.models import Model, ModelConfig

__all__ = ["ServeConfig", "ServingEngine", "sample_greedy", "sample_topk"]


def sample_greedy(key, logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def sample_topk(key, logits, k: int = 40, temperature: float = 0.8):
    v, idx = jax.lax.top_k(logits, k)
    v = v / temperature
    choice = jax.random.categorical(key, v, axis=-1)
    return jnp.take_along_axis(idx, choice[..., None], axis=-1)[..., 0].astype(
        jnp.int32
    )


@dataclasses.dataclass
class ServeConfig:
    max_context: int = 512
    batch: int = 4
    sampler: str = "greedy"  # greedy | topk


class ServingEngine:
    """One model, fixed batch slots, continuous decode.

    Optionally registers with the online scheduler: ``rt_register`` asks a
    :class:`repro.sched.DynamicController` — or a fleet-level
    :class:`repro.sched.CapacityBroker`, which places the service on
    whichever host certifies it — to admit this engine's periodic decode
    service (converted to an RTGPU task via the roofline-derived chain in
    ``repro.runtime.task_spec``), and ``rt_deregister`` departs through
    the mode-change protocol (slices reclaimed at the job boundary, never
    mid-request).
    """

    def __init__(self, cfg: ModelConfig, serve: ServeConfig, params=None,
                 seed: int = 0):
        self.cfg = cfg
        self.serve = serve
        self._rt = None            # (controller, service name) when admitted
        self.model = Model(cfg)
        key = jax.random.PRNGKey(seed)
        self.params = params if params is not None else self.model.init_params(key)
        sample = sample_greedy if serve.sampler == "greedy" else sample_topk
        n_extra = int(bool(cfg.n_patches)) + int(cfg.is_encoder_decoder)
        model = self.model

        # The sampler runs inside both programs, on the logits of the last
        # position: each returns the next token, [B, 1] int32.  ``key`` is
        # empty for greedy and ``(key,)`` for topk; the step's key is folded
        # from it and the position whose logits are sampled, so the host
        # passes one key per call and splits nothing.
        def next_token(logits, key, pos):
            sub = jax.random.fold_in(key[0], pos) if key else None
            return sample(sub, logits[:, -1, :])[:, None]

        @jax.jit
        def prefill_fn(params, tokens, caches, *extra):
            kw = {}
            if cfg.n_patches:
                kw["extra_embeds"] = extra[0]
            if cfg.is_encoder_decoder:
                kw["enc_embeds"] = extra[n_extra - 1]
            with jax.named_scope("prefill"):
                logits, caches, _ = model.prefill(params, tokens, caches, **kw)
                pos = cfg.n_patches + tokens.shape[1] - 1
                return next_token(logits, extra[n_extra:], pos), caches

        @jax.jit
        def decode_fn(params, token, caches, cache_len, *key):
            with jax.named_scope("decode"):
                logits, caches = model.decode_step(
                    params, token, caches, cache_len)
                return next_token(logits, key, cache_len[0]), caches

        # a job's fresh caches and zero patch / encoder embeddings, in one
        # program per (batch, max_context)
        @jax.jit
        def alloc_fn():
            b = serve.batch
            zeros = []
            if cfg.n_patches:
                zeros.append(jnp.zeros((b, cfg.n_patches, cfg.d_model),
                                       jnp.float32))
            if cfg.is_encoder_decoder:
                zeros.append(jnp.zeros((b, cfg.enc_ctx, cfg.d_model),
                                       jnp.float32))
            return model.init_caches(b, serve.max_context), zeros

        self._prefill = prefill_fn
        self._decode = decode_fn
        self._alloc = alloc_fn
        # topk's key when the caller gives none; greedy takes no key
        self._key0 = None if serve.sampler == "greedy" else (
            jax.random.PRNGKey(0))
        self._cache_lens: dict[int, jax.Array] = {}

    def _cache_len(self, n: int) -> jax.Array:
        """``[batch]`` int32 on the device, all ``n``: made once per length
        (every job of a service repeats the same lengths), so a decode step
        adds no eager op."""
        if n not in self._cache_lens:
            self._cache_lens[n] = jax.device_put(
                np.full((self.serve.batch,), n, np.int32))
        return self._cache_lens[n]

    # ---- online-scheduler registration --------------------------------------

    def rt_register(self, controller, spec, t: float = 0.0):
        """Admit this engine as an RT service on ``controller``
        (:class:`repro.sched.DynamicController`, a multi-host
        :class:`repro.sched.CapacityBroker`, or the static
        :class:`repro.runtime.AdmissionController`).  Returns the
        controller's decision (a ``BrokerDecision`` names the placed host
        for brokers); on success the engine remembers its registration for
        :meth:`rt_deregister`."""
        from repro.runtime.task_spec import serving_task_to_rt

        task = serving_task_to_rt(spec)
        if hasattr(controller, "job_boundary"):   # online ctl/broker: clocked
            dec = controller.admit(task, t=t)
        else:                                     # static wrapper front door
            dec = controller.admit(task)
        if dec.admitted:
            self._rt = (controller, spec.name)
        return dec

    def rt_deregister(self, t: float = 0.0) -> bool:
        """Depart from the scheduler (job-boundary reclamation)."""
        if self._rt is None:
            return False
        controller, name = self._rt
        self._rt = None
        if hasattr(controller, "release"):
            return controller.release(name, t=t)
        return controller.remove(name)

    @property
    def rt_registered(self) -> bool:
        return self._rt is not None

    def generate(
        self,
        prompts: np.ndarray,           # [B, S] int32
        max_new_tokens: int = 16,
        extra_embeds=None,
        enc_embeds=None,
        key=None,
    ) -> tuple[np.ndarray, dict]:
        b, s = prompts.shape
        assert b == self.serve.batch
        # Each span names the RTGPU segment its work belongs to (the chain
        # ``runtime.task_spec.serving_task_to_rt`` builds): ``cpu`` for host
        # work, ``copy`` for host<->device transfers, ``device`` for a
        # program from dispatch to ``block_until_ready``.
        with span("engine.generate", batch=b, new_tokens=max_new_tokens):
            with span("engine.init_caches", segment="cpu"):
                caches, extra = self._alloc()
                if self.cfg.n_patches and extra_embeds is not None:
                    extra[0] = extra_embeds
                if self.cfg.is_encoder_decoder and enc_embeds is not None:
                    extra[-1] = enc_embeds
                keys = () if self._key0 is None else (
                    self._key0 if key is None else key,)

            # timings end in block_until_ready: they measure the device, not
            # the enqueue of an asynchronous dispatch
            t0 = time.perf_counter()
            with span("engine.upload", segment="copy"):
                tokens = jnp.asarray(prompts)
            with span("engine.prefill", segment="device"):
                tok, caches = self._prefill(
                    self.params, tokens, caches, *extra, *keys)
                tok.block_until_ready()
            prefill_s = time.perf_counter() - t0

            out = np.zeros((b, max_new_tokens), np.int32)
            start = s + self.cfg.n_patches
            decode_t = []
            for i in range(max_new_tokens):
                with span("engine.pull", segment="copy", step=i):
                    out[:, i] = np.asarray(tok)[:, 0]
                cache_len = self._cache_len(start + i)
                t1 = time.perf_counter()
                with span("engine.decode", segment="device", step=i):
                    tok, caches = self._decode(
                        self.params, tok, caches, cache_len, *keys)
                    tok.block_until_ready()
                decode_t.append(time.perf_counter() - t1)
        stats = {
            "prefill_s": prefill_s,
            "decode_s_per_tok": float(np.mean(decode_t)) if decode_t else 0.0,
            "tokens": b * max_new_tokens,
        }
        return out, stats
