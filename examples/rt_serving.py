"""RT serving: admission-controlled multi-model inference (the paper's
use case — several AI tasks sharing one accelerator with hard deadlines).

  PYTHONPATH=src python examples/rt_serving.py

Three model services (reduced configs of assigned archs) ask for admission
with different periods/deadlines.  The controller sizes each service's
dedicated chip-slice allocation via Algorithm 2; admitted services then run
REAL prefill+decode steps through the serving engine while the discrete-
event runtime validates the timing model.
"""
import numpy as np

from repro.configs import get_smoke_config
from repro.launch import use_compile_cache
from repro.runtime import AdmissionController, ServingTaskSpec, serving_task_to_rt, simulate
from repro.serving import ServeConfig, ServingEngine


def main():
    print(f"compile cache: {use_compile_cache()}")
    ac = AdmissionController(gn_total=12)

    services = [
        ServingTaskSpec(
            name="chat-qwen", arch_id="qwen3-0.6b", period_ms=50.0,
            deadline_ms=40.0, batch=4, seq_len=256, new_tokens=3,
            roofline_step_s=0.002, collective_s=2e-4, dominant="compute_s",
        ),
        ServingTaskSpec(
            name="vision-internvl", arch_id="internvl2-2b", period_ms=100.0,
            deadline_ms=80.0, batch=2, seq_len=512, new_tokens=2,
            roofline_step_s=0.004, collective_s=3e-4, dominant="memory_s",
        ),
        ServingTaskSpec(
            name="audio-whisper", arch_id="whisper-base", period_ms=200.0,
            deadline_ms=150.0, batch=2, seq_len=128, new_tokens=4,
            roofline_step_s=0.001, collective_s=1e-4, dominant="compute_s",
        ),
        ServingTaskSpec(  # an aggressive latecomer that should be rejected
            name="greedy-batch", arch_id="dbrx-132b", period_ms=8.0,
            deadline_ms=6.0, batch=64, seq_len=2048, new_tokens=4,
            roofline_step_s=0.050, collective_s=1e-3, dominant="compute_s",
        ),
    ]

    for spec in services:
        task = serving_task_to_rt(spec)
        dec = ac.admit(task)
        verdict = "ADMITTED" if dec.admitted else f"REJECTED ({dec.reason})"
        print(f"{spec.name:18s} T={spec.period_ms:6.1f}ms D={spec.deadline_ms:6.1f}ms -> {verdict}")
        if dec.admitted:
            print(f"{'':18s} slice allocation now: {dec.alloc}")

    ts = ac.current_taskset()
    sim = simulate(ts, ac.current_alloc_list(), horizon=5000.0, seed=0)
    print(f"\nruntime check over 5 s: misses={sim.misses} jobs={sim.jobs}")
    assert not sim.any_miss

    # run REAL decode steps for one admitted service
    cfg = get_smoke_config("qwen3-0.6b")
    engine = ServingEngine(cfg, ServeConfig(max_context=128, batch=4))
    prompts = np.random.default_rng(0).integers(0, cfg.vocab, (4, 32)).astype(np.int32)
    out, stats = engine.generate(prompts, max_new_tokens=8)
    print(f"\nchat-qwen real decode: {out.shape[1]} tokens/slot, "
          f"prefill {stats['prefill_s']*1e3:.1f} ms, "
          f"decode {stats['decode_s_per_tok']*1e3:.1f} ms/tok")
    print("sampled ids:", out[0].tolist())


if __name__ == "__main__":
    main()
