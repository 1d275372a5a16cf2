"""``run.py``'s whole main on the CPU at a tiny size: set-up, window, tally,
check and the result line, untraced and traced.  Only its look for a chip is
stepped round, and the program's smoke config stands in for the published
one.  With a decode step that keeps its cache, ``correct`` comes out false."""
import importlib.util
import json

import jax
import pytest

import harness
import registry
import test_bench_cell as smoke

CELL = "qwen3-0.6b.control"


@pytest.fixture
def run_mod(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    spec = importlib.util.spec_from_file_location(
        "bench_run_under_test", registry.BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    keep = {k: getattr(jax.config, k) for k in (
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    monkeypatch.setattr(mod, "chips_or_exit", lambda chips: jax.devices())
    monkeypatch.setattr(mod, "TRACE_S", 1.0)
    cell_dict, config_dict = smoke.smoke_dicts(monkeypatch)
    monkeypatch.setattr(registry, "workload", lambda name: cell_dict)
    monkeypatch.setattr(registry, "config", lambda name: config_dict)
    monkeypatch.setattr(registry, "peaks", lambda kind: {
        "bf16_flops_per_s": 1e12})
    yield mod
    for k, v in keep.items():
        jax.config.update(k, v)


def _result(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def test_untraced_run_prints_end_to_end_metrics(run_mod, capsys):
    assert run_mod.main(["--workload", CELL, "--seed", str(2**32 + 3),
                         "--seconds", "1.5", "--trace", "0"]) == 0
    res = _result(capsys)
    assert list(res)[:3] == ["correct", "attempted", "failed"]
    assert list(res)[-1] == "compared"
    assert res["correct"] is True
    assert res["attempted"] >= 5
    assert set(res["metrics"]) == {"response_ms_p50", "setup_s"}
    assert res["metrics"]["response_ms_p50"]["unit"] == "ms"
    assert res["device"]["platform"] == "cpu"
    assert set(res["compared"]) == {"control.gap"}


def test_traced_run_reports_what_the_trace_holds(run_mod, capsys):
    assert run_mod.main(["--workload", CELL, "--seed", "7",
                         "--seconds", "1.5", "--trace", "1"]) == 0
    res = _result(capsys)
    # a CPU trace has the benchmark's spans and no device plane: the device
    # readers report nothing, the executor's readers report wait and tail
    assert set(res["metrics"]) == {"executor_wait_ms_p95",
                                   "response_tail_ms_p95"}
    assert res["device"]["busy_s"] == 0.0 and res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_broken_decode_reads_not_correct(run_mod, capsys, monkeypatch):
    build = harness.Cell.build

    def broken(self):
        build(self)
        for s in self.services:
            decode = s.engine._decode
            s.engine._decode = (lambda p, t, c, n, d=decode:
                                (d(p, t, c, n)[0], c))

    monkeypatch.setattr(harness.Cell, "build", broken)
    assert run_mod.main(["--workload", CELL, "--seed", "8",
                         "--seconds", "1.0", "--trace", "0"]) == 0
    res = _result(capsys)
    assert res["correct"] is False
    v = res["compared"]["control.gap"]
    assert v["value"] > v["limit"]
