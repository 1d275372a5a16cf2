"""Trace reduction: busy intervals, idle inside job spans, device time per
program, on a synthetic trace and on one recorded on the CPU."""
import jax
import jax.numpy as jnp
import pytest

import registry
import tracefile
from tracefile import Event

DEV, HOST = "/device:TPU:0", "/host:CPU"


def _synthetic():
    ops = [(0, 10), (5, 15), (20, 30), (50, 60), (95, 120)]
    ev = [Event(DEV, tracefile.OPS_LINE, f"fusion.{i}", s, e - s)
          for i, (s, e) in enumerate(ops)]
    ev += [Event(DEV, tracefile.MODULES_LINE, "jit_prefill_fn(1)", 0, 15),
           Event(DEV, tracefile.MODULES_LINE, "jit_decode_fn(2)", 20, 10),
           Event(DEV, tracefile.MODULES_LINE, "jit_decode_fn(2)", 50, 10)]
    ev += [Event(HOST, "python", "job:a", 0, 40),
           Event(HOST, "python", "job:b", 45, 20),
           Event(HOST, "python", tracefile.WINDOW_SPAN, 0, 100)]
    return ev


def test_union_covered_gaps():
    merged = tracefile.union([(5, 15), (0, 10), (20, 30), (30, 31)])
    assert merged == [(0, 15), (20, 31)]
    assert tracefile.covered(merged, 0, 100) == 26
    assert tracefile.covered(merged, 12, 25) == 3 + 5
    assert tracefile.gaps(merged, 0, 40) == [(15, 20), (31, 40)]
    assert tracefile.gaps([], 3, 7) == [(3, 7)]


def test_reduce_synthetic_trace():
    r = tracefile.reduce(_synthetic())
    assert r.devices == 1
    assert r.busy == [(0, 15), (20, 30), (50, 60), (95, 120)]
    assert r.window == (0, 100)
    assert r.jobs == [("a", 0, 40), ("b", 45, 65)]
    assert [m.service for m in r.modules] == ["a", "a", "b"]
    # busy in the window: 15 + 10 + 10 + 5 ns
    assert tracefile.device_busy_s(r) == pytest.approx(40e-9)
    # idle 15..20 and 30..50 and 60..95, cut at the job edges 40, 45, 65
    got = [(n, round(g * 1e9, 6)) for n, g in tracefile.top_gaps(r)]
    assert got[:2] == [("between jobs", 30.0), ("in job:a", 10.0)]
    assert sorted(got[2:]) == [("between jobs", 5.0), ("in job:a", 5.0),
                               ("in job:b", 5.0), ("in job:b", 5.0)]
    assert tracefile.top_ops(r)[0] == ["fusion.0", 10e-9]


def test_metric_readers_on_synthetic_trace():
    r = tracefile.reduce(_synthetic())
    record = {"trace": r, "jobs": [], "services": {}, "peak": {}}
    idle = registry.metric("job_device_idle_share").read(record)
    # job a: 40 ns, 25 busy; job b: 20 ns, 10 busy -> 25 idle of 60
    assert idle == pytest.approx(100 * 25 / 60)
    assert registry.metric("decode_step_ms").read(record) == pytest.approx(
        10e-6)


class _Svc:
    def __init__(self, cost, dims, spec):
        self.model = type("M", (), {"cost": cost, "dims": dims})()
        self.spec = spec


def test_step_mfu_charges_each_execution_to_its_service():
    cost = registry.cost("dense")
    dims = registry.family("dense").dims({
        "model_type": "qwen3", "vocab_size": 64, "hidden_size": 32,
        "intermediate_size": 64, "num_hidden_layers": 1,
        "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 16,
        "rope_theta": 1e4, "tie_word_embeddings": True})
    spec = {"batch": 2, "prompt_len": 8, "new_tokens": 2}
    svc = _Svc(cost, dims, spec)
    r = tracefile.reduce(_synthetic())
    record = {"trace": r, "jobs": [], "peak": {"bf16_flops_per_s": 1e12},
              "services": {"a": svc, "b": svc}}
    flops = (cost.prefill_flops(dims, 2, 8)
             + 2 * (cost.decode_flops(dims, 2, 9)
                    + cost.decode_flops(dims, 2, 10)) / 2)
    want = 100 * flops / (35e-9 * 1e12)
    assert registry.metric("step_mfu").read(record) == pytest.approx(want)


def test_cpu_recorded_trace(tmp_path):
    """A trace recorded here has the benchmark's host spans and no device
    plane: the device readers then report nothing, never 0."""
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tracefile.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("job:svc"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    r = tracefile.reduce(tracefile.load(tracefile.find_xplane(tmp_path)))
    assert [j[0] for j in r.jobs] == ["svc"] * 3
    assert r.window[1] > r.window[0]
    assert all(r.window[0] <= s <= e <= r.window[1] for _, s, e in r.jobs)
    assert r.devices == 0 and tracefile.device_busy_s(r) == 0.0
    record = {"trace": r, "jobs": [], "services": {}, "peak": {}}
    for name in ("job_device_idle_share", "decode_step_ms", "step_mfu"):
        assert registry.metric(name).read(record) is None
