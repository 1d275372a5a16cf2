"""Bring-up smoke test on one TPU chip: the admitted serving path at the
full published width of qwen3-0.6b.

  python chip_smoke.py [--seed N]

Everything runs in this one process, which holds the chip:

  1. kernels   persistent_matmul and flash_attention at qwen3-0.6b widths,
               selective_scan at jamba-52b widths, each compiled for the chip
               (its HLO holds a ``tpu_custom_call``) and compared with
               ``repro.kernels.ref``;
  2. model     ServingEngine on qwen3-0.6b in bf16 (28 layers, d_model 1024,
               GQA 16/8, head_dim 128, vocab 151936) with random weights from
               the seed; warm-up compiles prefill and decode, then the warm
               decode step is timed on the device;
  3. logits    last-position prefill logits in bf16 against a float32 run of
               the same parameters and prompts;
  4. admission DynamicController admits the service, its GPU segment built
               from the decode step just measured;
  5. executor  WallClockExecutor runs the admitted service for a few seconds,
               each job one ``engine.generate`` on prompts drawn from the seed.

Any failed phase, error out of tolerance or missed deadline exits non-zero,
as does a first device that is not a TPU.  The last line of standard output
is then ``{"ok": true, "device": {...}}``, and only then.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import set_backend  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.flash_attention import flash_attention  # noqa: E402
from repro.launch import use_compile_cache  # noqa: E402
from repro.models import Model  # noqa: E402
from repro.runtime import (  # noqa: E402
    Service, ServingTaskSpec, WallClockExecutor, serving_task_to_rt,
)
from repro.sched import DynamicController, EventTrace  # noqa: E402
from repro.serving import ServeConfig, ServingEngine  # noqa: E402

ARCH = "qwen3-0.6b"
SERVE = ServeConfig(max_context=512, batch=4)
PROMPT_LEN = 64
NEW_TOKENS = 8
WARM_RUNS = 5
# Period = deadline = MARGIN x the worst warm job time measured here.
MARGIN = 3.0
MIN_REQUESTS = 5
MIN_DURATION_S = 3.0

# Kernel widths: qwen3-0.6b's MLP up-projection (d_model 1024 -> d_ff 3072)
# and attention (batch 2 x 16 heads, 2048 tokens, head_dim 128); jamba-52b's
# selective scan (d_inner 8192, d_state 16) over 1024 steps.
KERNEL_SHAPES = {
    "matmul": (1024, 1024, 3072),
    "flash": (32, 2048, 128),
    "scan": (1, 1024, 8192, 16),
}
# Max |kernel - ref| / max |ref|.  bf16 outputs round to 2^-8 relative, so
# one-ulp disagreements from a different f32 accumulation order stay under
# 1e-2; flash also rounds probabilities to bf16 before P.V (ref does so
# after normalizing).  The scan is float32 end to end.
KERNEL_TOL = {"matmul": 1e-2, "flash": 3e-2, "scan": 1e-4}
# Max |bf16 - f32| / max |f32| of the last-position logits.
LOGITS_TOL = 5e-2


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def rel_err(got, want) -> tuple[float, float]:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(bool(np.isfinite(got).all() and np.isfinite(want).all()),
          "non-finite values")
    err = float(np.max(np.abs(got - want)))
    return err, err / max(float(np.max(np.abs(want))), 1e-30)


def check_kernels(seed: int, interpret: bool = False) -> None:
    """Each kernel compiled once, run, and held to its oracle."""
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    bf16, f32 = jnp.bfloat16, jnp.float32
    m, kd, n = KERNEL_SHAPES["matmul"]
    bh, s, hd = KERNEL_SHAPES["flash"]
    b, ss, d, ds = KERNEL_SHAPES["scan"]
    cases = {
        "matmul": (
            lambda x, w: ops.pinned_matmul(x, w, interpret=interpret),
            ref.matmul_ref,
            (jax.random.normal(k[0], (m, kd), bf16),
             jax.random.normal(k[1], (kd, n), bf16)),
        ),
        "flash": (
            lambda q, kk, v: flash_attention(q, kk, v, scale=hd ** -0.5,
                                             interpret=interpret),
            lambda q, kk, v: ref.flash_attention_ref(q, kk, v,
                                                     scale=hd ** -0.5),
            tuple(jax.random.normal(k[2 + i], (bh, s, hd), bf16)
                  for i in range(3)),
        ),
        "scan": (
            lambda a, x, c: ops.mamba_scan(a, x, c, interpret=interpret),
            ref.selective_scan_ref,
            (jax.nn.sigmoid(jax.random.normal(k[5], (b, ss, d, ds), f32)),
             jax.random.normal(k[6], (b, ss, d, ds), f32) * 0.1,
             jax.random.normal(k[7], (b, ss, ds), f32)),
        ),
    }
    for name, (kernel, oracle, args) in cases.items():
        compiled = jax.jit(kernel).lower(*args).compile()
        custom = "tpu_custom_call" in compiled.as_text()
        check(custom or interpret, f"{name}: no tpu_custom_call in its HLO")
        got = compiled(*args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(oracle)(*args)
        err, rel = rel_err(got, want)
        print(f"kernel {name:6s} {tuple(args[0].shape)} {args[0].dtype}: "
              f"tpu_custom_call={custom} max_abs_err={err!r} "
              f"rel_err={rel!r} tol={KERNEL_TOL[name]}")
        check(rel <= KERNEL_TOL[name], f"{name}: rel_err {rel} > tol")


def check_logits(engine: ServingEngine, prompts: np.ndarray) -> None:
    """bf16 prefill logits vs float32 of the same parameters and prompts."""
    cfg, model = engine.cfg, engine.model
    b, s = prompts.shape
    tokens = jnp.asarray(prompts)
    low, _, _ = jax.jit(model.prefill)(engine.params, tokens,
                                       model.init_caches(b, s))
    model32 = Model(dataclasses.replace(cfg, dtype="float32"))
    params32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                      engine.params)
    with jax.default_matmul_precision("highest"):
        high, _, _ = jax.jit(model32.prefill)(params32, tokens,
                                              model32.init_caches(b, s))
    del params32
    err, rel = rel_err(low[:, -1], high[:, -1])
    print(f"logits {low.dtype} vs float32 {tuple(high[:, -1].shape)}: "
          f"max_abs_err={err!r} rel_err={rel!r} tol={LOGITS_TOL}")
    check(rel <= LOGITS_TOL, f"logits: rel_err {rel} > tol")


def serve(cfg, seed: int) -> None:
    serve_cfg, prompt_len, new_tokens = SERVE, PROMPT_LEN, NEW_TOKENS
    t0 = time.perf_counter()
    engine = ServingEngine(cfg, serve_cfg, seed=seed)
    jax.block_until_ready(engine.params)
    init_s = time.perf_counter() - t0
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(engine.params))
    print(f"model {cfg.name}: {cfg.n_layers} layers d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim={cfg.head_dim} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab} dtype={cfg.dtype} "
          f"params={n_params} batch={serve_cfg.batch} "
          f"max_context={serve_cfg.max_context} (init {init_s!r} s)")
    rng = np.random.default_rng(seed)

    def draw():
        return rng.integers(0, cfg.vocab, (serve_cfg.batch, prompt_len),
                            dtype=np.int32)

    t0 = time.perf_counter()
    engine.generate(draw(), max_new_tokens=new_tokens)
    print(f"warm-up (compiles prefill + decode): "
          f"{time.perf_counter() - t0!r} s")

    check_logits(engine, draw())
    gc.collect()   # the float32 copy goes before anything is timed

    steps, prefills, jobs = [], [], []
    for _ in range(WARM_RUNS):
        t0 = time.perf_counter()
        _, stats = engine.generate(draw(), max_new_tokens=new_tokens)
        jobs.append(time.perf_counter() - t0)
        steps.append(stats["decode_s_per_tok"])
        prefills.append(stats["prefill_s"])
    step_s, job_s = max(steps), max(jobs)
    print(f"warm prefill ({prompt_len} tokens, block_until_ready): "
          f"{prefills!r} s")
    print(f"warm decode step (block_until_ready, mean per token, "
          f"{WARM_RUNS} runs): {steps!r} s -> max {step_s!r} s")
    print(f"warm job ({prompt_len}-token prefill + {new_tokens} decode "
          f"steps): {jobs!r} s -> {job_s!r} s")

    period_ms = MARGIN * job_s * 1e3
    spec = ServingTaskSpec(
        name=cfg.name, arch_id=ARCH, period_ms=period_ms,
        deadline_ms=period_ms, batch=serve_cfg.batch, seq_len=prompt_len,
        new_tokens=new_tokens, roofline_step_s=step_s, vocab=cfg.vocab,
        dominant="memory_s",
    )
    controller = DynamicController(gn_total=1)   # the one chip is one slice
    dec = controller.admit(serving_task_to_rt(spec))
    print(f"admission: admitted={dec.admitted} alloc={dec.alloc} "
          f"bounds_ms={dec.bounds} path={dec.path!r} reason={dec.reason!r} "
          f"(step {step_s!r} s, period = deadline = {period_ms!r} ms)")
    check(dec.admitted, f"service rejected: {dec.reason}")

    svc = Service(
        spec.name, period_s=period_ms / 1e3, deadline_s=period_ms / 1e3,
        run_job=lambda: engine.generate(draw(), max_new_tokens=new_tokens),
    )
    duration = max(MIN_DURATION_S, 2 * MIN_REQUESTS * svc.period_s)
    trace = EventTrace(us_per_unit=1e6)
    st = WallClockExecutor([svc], trace=trace).run(duration_s=duration)
    st = st[spec.name]
    responses = [dict(ev.meta)["response_s"] * 1e3 for ev in trace.events
                 if ev.kind == "complete"]
    print(f"executor {duration!r} s: released={st['released']} "
          f"completed={st['completed']} missed={st['missed']} "
          f"worst_response_ms={st['worst_response_ms']!r} "
          f"certified_bound_ms={dec.bounds[spec.name]!r}")
    print(f"responses_ms={responses!r}")
    check(st["completed"] >= MIN_REQUESTS,
          f"only {st['completed']} requests answered")
    check(st["missed"] == 0, f"{st['missed']} deadline(s) missed")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, the first device is {dev.platform}",
              file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    print(f"compile cache: {use_compile_cache()}")
    print(f"analysis backend: {set_backend('numpy')} "
          f"(jax_enable_x64={jax.config.jax_enable_x64})")
    try:
        check_kernels(args.seed)
        serve(get_config(ARCH), args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
