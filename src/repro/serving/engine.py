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
        self._sample = sample_greedy if serve.sampler == "greedy" else sample_topk

        model = self.model

        @jax.jit
        def prefill_fn(params, tokens, caches, *extra):
            kw = {}
            i = 0
            if cfg.n_patches:
                kw["extra_embeds"] = extra[i]; i += 1
            if cfg.is_encoder_decoder:
                kw["enc_embeds"] = extra[i]; i += 1
            with jax.named_scope("prefill"):
                logits, caches, _ = model.prefill(params, tokens, caches, **kw)
            return logits, caches

        @jax.jit
        def decode_fn(params, token, caches, cache_len):
            with jax.named_scope("decode"):
                return model.decode_step(params, token, caches, cache_len)

        self._prefill = prefill_fn
        self._decode = decode_fn

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
                key = key if key is not None else jax.random.PRNGKey(0)
                caches = self.model.init_caches(b, self.serve.max_context)
                extra = []
                offset = 0
                if self.cfg.n_patches:
                    if extra_embeds is None:
                        extra_embeds = jnp.zeros(
                            (b, self.cfg.n_patches, self.cfg.d_model),
                            jnp.float32)
                    extra.append(extra_embeds)
                    offset = self.cfg.n_patches
                if self.cfg.is_encoder_decoder:
                    if enc_embeds is None:
                        enc_embeds = jnp.zeros(
                            (b, self.cfg.enc_ctx, self.cfg.d_model),
                            jnp.float32)
                    extra.append(enc_embeds)

            # timings end in block_until_ready: they measure the device, not
            # the enqueue of an asynchronous dispatch
            t0 = time.perf_counter()
            with span("engine.upload", segment="copy"):
                tokens = jnp.asarray(prompts)
            with span("engine.prefill", segment="device"):
                logits, caches = self._prefill(
                    self.params, tokens, caches, *extra)
                logits.block_until_ready()
            prefill_s = time.perf_counter() - t0

            with span("engine.sample", segment="cpu", step=0):
                out = np.zeros((b, max_new_tokens), np.int32)
                cache_len = jnp.full((b,), s + offset, jnp.int32)
                tok = self._sample(key, logits[:, -1, :])[:, None]
            decode_t = []
            for i in range(max_new_tokens):
                with span("engine.pull", segment="copy", step=i):
                    out[:, i] = np.asarray(tok[:, 0])
                t1 = time.perf_counter()
                with span("engine.decode", segment="device", step=i):
                    logits, caches = self._decode(
                        self.params, tok, caches, cache_len)
                    logits.block_until_ready()
                decode_t.append(time.perf_counter() - t1)
                with span("engine.sample", segment="cpu", step=i + 1):
                    cache_len = cache_len + 1
                    key, sub = jax.random.split(key)
                    tok = self._sample(sub, logits[:, -1, :])[:, None]
        stats = {
            "prefill_s": prefill_s,
            "decode_s_per_tok": float(np.mean(decode_t)) if decode_t else 0.0,
            "tokens": b * max_new_tokens,
        }
        return out, stats
