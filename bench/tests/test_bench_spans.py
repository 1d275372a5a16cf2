"""The program's spans and compile counter as ``bench/spans.py`` reads them:
idle put down to the innermost span on a synthetic trace, the benchmark's
own reduction untouched by the extra spans, the four quantities on a
synthetic record, and the whole run on the CPU at a tiny size."""
import json

import jax
import pytest

import registry
import spans
import test_bench_cell as smoke
import tracefile
from tracefile import Event

DEV, HOST = "/device:TPU:0", "/host:CPU"
OPS = [(0, 10), (5, 15), (20, 30), (50, 60), (95, 120)]


def _base():
    """The benchmark's rows (as in ``test_bench_trace``): device ops and
    programs, job and window spans."""
    rows = [(Event(DEV, tracefile.OPS_LINE, f"%fusion.{i} = f32[2] fusion()",
                   s, e - s), {"device_duration_ps": 1000 * (e - s)})
            for i, (s, e) in enumerate(OPS)]
    rows += [(Event(DEV, tracefile.MODULES_LINE, "jit_prefill_fn(1)", 0, 15),
              {}),
             (Event(DEV, tracefile.MODULES_LINE, "jit_decode_fn(2)", 20, 10),
              {}),
             (Event(DEV, tracefile.MODULES_LINE, "jit_decode_fn(2)", 50, 10),
              {})]
    rows += [(Event(HOST, "python", "job:a", 0, 40), {}),
             (Event(HOST, "python", "job:b", 45, 20), {}),
             (Event(HOST, "python", tracefile.WINDOW_SPAN, 0, 100), {})]
    return rows


# the compiled programs' HLO, as ``Compiled.as_text()`` prints it: the same
# instruction names in both modules, under different scopes
HLO = {
    "prefill": """HloModule jit_prefill_fn, is_scheduled=true
  %fusion.0 = f32[2] fusion(), kind=kLoop, metadata={op_name="jit(prefill_fn)/prefill/while/body/closed_call/attention/dot_general" stack_frame_id=3}
  %fusion.1 = f32[2] fusion(), kind=kLoop, metadata={op_name="jit(prefill_fn)/prefill/while/body/closed_call/attention/kv_update/dynamic_update_slice"}
  ROOT %fusion.2 = f32[2] fusion(), kind=kLoop, metadata={op_name="jit(prefill_fn)/prefill/while/body/dynamic_slice"}
""",
    "decode": """HloModule jit_decode_fn, entry_computation_layout={()->f32[2]}
  %fusion.2 = f32[2] fusion(), kind=kLoop, metadata={op_name="jit(decode_fn)/decode/while/body/closed_call/attention/kv_update/select_n"}
  %fusion.3 = f32[2] fusion(), kind=kLoop, metadata={op_name="jit(decode_fn)/decode/lm_head/dot_general"}
  %fusion.4 = f32[2] fusion(), kind=kLoop
  %copy.1 = f32[2] copy(), metadata={op_name="jit(decode_fn)/mul"}
""",
}


# (name, start, end, stats): job a holds a host.gc inside a sample step;
# job b leaves 1 ns at each end under no program span
PROGRAM = [
    ("executor.job", 0, 41, {"service": "a", "wait_us": 0.5}),
    ("engine.generate", 1, 39, {"batch": 4, "new_tokens": 1}),
    ("engine.init_caches", 1, 3, {"segment": "cpu"}),
    ("engine.prefill", 3, 16, {"segment": "device"}),
    ("engine.sample", 16, 18, {"segment": "cpu", "step": 0}),
    ("engine.pull", 18, 20, {"segment": "copy", "step": 0}),
    ("engine.decode", 20, 31, {"segment": "device", "step": 0}),
    ("engine.sample", 31, 36, {"segment": "cpu", "step": 1}),
    ("host.gc", 32, 34, {"generation": 0}),
    ("executor.idle", 41, 44, {}),
    ("executor.job", 46, 64, {"service": "b", "wait_us": 1.0}),
    ("engine.generate", 46, 64, {"batch": 4, "new_tokens": 0}),
    ("engine.upload", 46, 48, {"segment": "copy"}),
    ("engine.prefill", 48, 61, {"segment": "device"}),
    ("engine.sample", 61, 63, {"segment": "cpu", "step": 0}),
    ("executor.idle", 66, 70, {}),
]


def _rows():
    return _base() + [(Event(HOST, "python", n, s, e - s), st)
                      for n, s, e, st in PROGRAM]


def test_gaps_go_to_the_innermost_span():
    rows = _rows()
    red = tracefile.reduce([e for e, _ in rows])
    sp = spans.spans(rows, red)
    assert len(sp) == len(PROGRAM)
    assert [s.job for s in sp if s.name == "executor.idle"] == [None, None]
    assert {s.job for s in sp if s.name.startswith("engine.")} == {0, 1}
    tl = spans.Timeline(sp)
    assert [p.name for p in tl.cut(30, 40)] == [
        "engine.decode", "engine.sample", "host.gc", "engine.sample",
        "engine.generate", "executor.job"]
    # the gc pause lies inside a sample step: its segment is the step's
    assert [p.segment for p in tl.cut(32, 34)] == ["cpu"]
    got = sorted((n, round(g * 1e9, 6))
                 for n, g in spans.name_gaps(red, tl, n=100))
    assert ("between jobs (no span)", 25.0) in got
    assert ("between jobs executor.idle", 4.0) in got
    assert ("in job:a engine.pull", 2.0) in got
    assert ("in job:a host.gc", 2.0) in got
    assert ("in job:b (no span)", 1.0) in got
    # the longest still starts as tracefile.top_gaps names it
    top = spans.name_gaps(red, tl, n=1)[0][0]
    assert top.startswith(tracefile.top_gaps(red, n=1)[0][0])


def test_segments_and_unexplained_add_up_to_the_in_job_idle():
    rows = _rows()
    red = tracefile.reduce([e for e, _ in rows])
    idle = spans.job_idle(red, spans.Timeline(spans.spans(rows, red)))
    # job a: idle 15..20, 30..40; job b: idle 45..50, 60..65
    assert idle[0] == {"idle": 15.0, "cpu": 7.0, "copy": 2.0, "device": 2.0,
                       "other": 4.0, "(no span)": 0.0}
    assert idle[1] == {"idle": 10.0, "cpu": 2.0, "copy": 2.0, "device": 3.0,
                       "other": 1.0, "(no span)": 2.0}
    for j in idle:
        assert sum(v for k, v in j.items() if k != "idle") == j["idle"]
    share = registry.metric("job_device_idle_share").read(
        {"trace": red, "jobs": [], "services": {}, "peak": {}})
    assert sum(j["idle"] for j in idle) == pytest.approx(share / 100 * 60)


def test_existing_reduction_and_metrics_ignore_the_program_spans():
    with_spans = tracefile.reduce([e for e, _ in _rows()])
    without = tracefile.reduce([e for e, _ in _base()])
    assert with_spans == without
    assert tracefile.top_gaps(with_spans) == tracefile.top_gaps(without)
    assert tracefile.top_ops(with_spans) == tracefile.top_ops(without)
    svc = type("S", (), {"spec": {"batch": 2, "prompt_len": 8,
                                  "new_tokens": 2}})()
    svc.model = type("M", (), {
        "cost": registry.cost("dense"),
        "dims": registry.family("dense").dims({
            "model_type": "qwen3", "vocab_size": 64, "hidden_size": 32,
            "intermediate_size": 64, "num_hidden_layers": 1,
            "num_attention_heads": 2, "num_key_value_heads": 1,
            "head_dim": 16, "rope_theta": 1e4,
            "tie_word_embeddings": True})})()
    jobs = [type("J", (), {"wait": 1e-3 * i, "response": 0.1 + 1e-3 * i})()
            for i in range(5)]
    for m in registry.benchmark()["per_layer"]:
        reader = registry.metric(m["name"])
        a, b = ({"trace": r, "jobs": jobs, "services": {"a": svc, "b": svc},
                 "peak": {"bf16_flops_per_s": 1e12}}
                for r in (with_spans, without))
        assert reader.read(a) == reader.read(b), m["name"]


def test_scoped_ops_keep_the_device_time():
    red = tracefile.reduce([e for e, _ in _rows()])
    scopes = dict(spans.hlo_scopes(t) for t in HLO.values())
    assert scopes == {
        "jit_prefill_fn": {"fusion.0": "prefill/attention",
                           "fusion.1": "prefill/kv_update",
                           "fusion.2": "prefill"},
        "jit_decode_fn": {"fusion.2": "decode/kv_update",
                          "fusion.3": "decode/lm_head"}}
    scoped = spans.scoped_ops(red, scopes, n=100)
    plain = tracefile.top_ops(red, n=100)
    assert sum(v for _, v in scoped) == pytest.approx(sum(v for _, v in plain))
    assert len(scoped) == len(plain)
    # each op under the module running as it starts; fusion.4 runs in none
    assert {k: round(v * 1e9, 6) for k, v in scoped} == {
        "prefill/attention: %fusion.0 = f32[2] fusion()": 10.0,
        "prefill/kv_update: %fusion.1 = f32[2] fusion()": 10.0,
        "decode/kv_update: %fusion.2 = f32[2] fusion()": 10.0,
        "decode/lm_head: %fusion.3 = f32[2] fusion()": 10.0,
        "%fusion.4 = f32[2] fusion()": 5.0}
    assert spans.scoped_ops(red, {}, n=100) == plain
    assert spans.scope_of("jit(g)/jit(main)/mul") == ""
    assert spans.scope_of("decode/while/body/mlp/jit(silu)/mul") == \
        "decode/mlp"


def test_compiled_engine_programs_carry_their_scopes():
    """On a real (tiny) engine the compiled HLO names the model scopes."""
    from repro.configs import get_smoke_config
    from repro.serving import ServeConfig, ServingEngine

    eng = ServingEngine(get_smoke_config("qwen3-0.6b"),
                        ServeConfig(max_context=24, batch=2))
    svc = type("S", (), {"engine": eng, "spec": {
        "batch": 2, "prompt_len": 16, "max_context": 24}})()
    scopes = spans.program_scopes(svc)
    assert set(scopes) == {"jit_prefill_fn", "jit_decode_fn"}
    for module, program in (("jit_prefill_fn", "prefill"),
                            ("jit_decode_fn", "decode")):
        got = set(scopes[module].values())
        for part in ("attention", "kv_update", "mlp", "lm_head"):
            assert f"{program}/{part}" in got, (module, part)


def test_quantities_on_a_synthetic_record():
    rows = _rows()
    red = tracefile.reduce([e for e, _ in rows])
    sp = spans.spans(rows, red)
    # job a: cpu spans 2 + 2 + 5 ns, copy 2; job b: cpu 2, copy 2
    assert spans.segment_ms(red, sp, "cpu") == pytest.approx(5.5e-6)
    assert spans.segment_ms(red, sp, "copy") == pytest.approx(2e-6)
    assert spans.segment_ms(red, [], "cpu") is None
    by_name = spans.span_ms_by_name(red, sp)
    assert by_name["engine.sample"] == pytest.approx((7 + 2) / 2 * 1e-6)
    assert by_name["host.gc"] == pytest.approx(1e-6)      # (2 + 0) / 2
    assert "executor.idle" not in by_name                 # between jobs
    snap = {"jax_compile_seconds_total": {"kind": "counter", "series": {
                "stage=backend": 2.0, "stage=cache_load": 1.5,
                "stage=lower": 0.5, "stage=trace": 0.25}},
            "jax_compiles_total": {"kind": "counter", "series": {
                "fun=jit(decode_fn)": 1.0, "fun=jit(prefill_fn)": 1.0}}}
    assert spans.compile_seconds(snap) == 2.75   # cache_load is in backend
    assert spans.compile_seconds({}) is None
    before = spans.compiles(snap)
    assert before == {"jit(decode_fn)": 1.0, "jit(prefill_fn)": 1.0}
    assert spans.compiles({}) is None
    after = dict(before, **{"jit(decode_fn)": 2.0, "jit(argmax)": 1.0})
    assert spans.compiles_between(before, after) == {
        "jit(decode_fn)": 1.0, "jit(argmax)": 1.0}
    assert spans.compiles_between(before, before) == {}


def test_run_on_the_cpu(monkeypatch, capsys, tmp_path):
    """The whole run at the smoke size: the CPU has no device plane, so
    every moment of a job is idle, and the program's spans cover it."""
    import run
    from repro.obs import metrics

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    keep = {k: getattr(jax.config, k) for k in (
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    monkeypatch.setattr(run, "chips_or_exit", lambda chips: jax.devices())
    cell, config = smoke.smoke_dicts(monkeypatch)
    monkeypatch.setattr(registry, "workload", lambda name: cell)
    monkeypatch.setattr(registry, "config", lambda name: config)
    monkeypatch.setattr(registry, "peaks", lambda kind: {
        "bf16_flops_per_s": 1e12})
    out = tmp_path / "spans.json"
    try:
        assert spans.main(["--workload", "qwen3-0.6b.control", "--seed",
                           str(2**32 + 11), "--seconds", "1", "--untraced",
                           "0.5", "--ab", "2", "--out", str(out)]) == 0
    finally:
        metrics.disable()
        for k, v in keep.items():
            jax.config.update(k, v)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert json.loads(out.read_text()) == res
    q = res["quantities"]
    assert q["job_cpu_segment_ms"] > 0 and q["job_copy_segment_ms"] > 0
    assert q["setup_compile_s"] > 0
    assert q["window_compiles"] == 0, res["compiled_in_window"]
    assert res["jobs_traced"] >= 2
    assert res["unexplained_share"] < 0.05
    assert all(g[0].split(" ")[-1].startswith(
        ("engine.", "executor.", "host.gc", "(no")) for g in res["idle_gaps"])
    assert res["ab"]["pairs"] == 2
    assert set(res["per_layer_as_run_reads_them"]) == {
        "executor_wait_ms_p95", "response_tail_ms_p95"}
