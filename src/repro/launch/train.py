"""Training driver.

On the production cluster this runs under the (pod, data, model) mesh; on
this CPU host it trains real (reduced) models end-to-end:

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \\
      --steps 50 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.launch.cache import use_compile_cache
from repro.models import Model
from repro.train.checkpoint import save_checkpoint
from repro.train.optimizer import AdamWConfig, adamw_update, init_opt_state


def train(
    arch: str,
    smoke: bool = True,
    steps: int = 50,
    batch: int = 8,
    seq: int = 128,
    lr: float = 3e-4,
    ckpt_dir: str | None = None,
    log_every: int = 10,
):
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    model = Model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init_params(key)
    opt_cfg = AdamWConfig(lr=lr, warmup_steps=max(steps // 10, 1),
                          total_steps=steps)
    opt_state = init_opt_state(params)
    data = TokenPipeline(DataConfig(vocab=cfg.vocab, seq_len=seq,
                                    global_batch=batch))

    extra = {}
    if cfg.n_patches:
        extra["extra_embeds"] = jnp.zeros((batch, cfg.n_patches, cfg.d_model),
                                          jnp.float32)
    if cfg.is_encoder_decoder:
        extra["enc_embeds"] = jnp.zeros((batch, cfg.enc_ctx, cfg.d_model),
                                        jnp.float32)

    @jax.jit
    def step_fn(params, opt_state, tokens, labels):
        loss, grads = jax.value_and_grad(
            lambda p: model.loss(p, tokens, labels, **extra)
        )(params)
        params, opt_state, metrics = adamw_update(opt_cfg, params, grads, opt_state)
        return params, opt_state, loss, metrics

    losses = []
    t0 = time.time()
    for step, (tokens, labels) in enumerate(data):
        if step >= steps:
            break
        params, opt_state, loss, metrics = step_fn(
            params, opt_state, jnp.asarray(tokens), jnp.asarray(labels)
        )
        losses.append(float(loss))
        if step % log_every == 0 or step == steps - 1:
            print(
                f"step {step:5d}  loss {float(loss):.4f}  "
                f"gnorm {float(metrics['grad_norm']):.3f}  "
                f"lr {float(metrics['lr']):.2e}  "
                f"{(time.time()-t0)/(step+1):.2f}s/step"
            )
    if ckpt_dir:
        save_checkpoint(ckpt_dir, steps, params, opt_state)
        print(f"checkpoint -> {ckpt_dir}")
    return losses


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen3-0.6b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args(argv)
    print(f"compile cache: {use_compile_cache()}")
    losses = train(args.arch, args.smoke, args.steps, args.batch, args.seq,
                   args.lr, args.ckpt_dir)
    print(f"first-10 mean {sum(losses[:10])/10:.4f} -> "
          f"last-10 mean {sum(losses[-10:])/10:.4f}")


if __name__ == "__main__":
    main()
