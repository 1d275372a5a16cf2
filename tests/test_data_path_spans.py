"""Data-path observability: the profiler spans ``ServingEngine.generate`` and
``WallClockExecutor.run`` write, the model scopes in the compiled programs,
and the JAX compile counter in ``repro.obs``."""
import gc
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import LayerSpec, ModelConfig
from repro.obs import compiles, metrics
from repro.runtime import Service, WallClockExecutor
from repro.sched import EventTrace
from repro.serving import ServeConfig, ServingEngine

PROGRAM_PREFIXES = ("engine.", "executor.", "host.")


def tiny_cfg():
    return ModelConfig(
        name="tiny", arch_type="dense", d_model=64, n_heads=4, n_kv_heads=2,
        d_ff=128, vocab=256, pattern=(LayerSpec("attn", "mlp"),),
        n_repeats=2, tie_embeddings=True, dtype="float32",
    )


def _prompts(b=2, s=12, vocab=256):
    return np.random.default_rng(0).integers(0, vocab, (b, s)).astype(
        np.int32)


def _recorded(fn, tmp_path):
    """Run ``fn`` under the profiler; its result and the program's host
    spans as (name, start_ns, end_ns, stats), by start."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.glob("**/*.xplane.pb"))[-1]
    data = jax.profiler.ProfileData.from_file(str(path))
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
              dict(ev.stats))
             for plane in data.planes if not plane.name.startswith("/device")
             for line in plane.lines for ev in line.events
             if ev.name.startswith(PROGRAM_PREFIXES)]
    return out, sorted(spans, key=lambda s: (s[1], -s[2]))


class TestEngineSpans:
    def test_generate_spans_nest_and_name_their_segment(self, tmp_path):
        eng = ServingEngine(tiny_cfg(), ServeConfig(max_context=32, batch=2))
        prompts, n = _prompts(), 4
        want, _ = eng.generate(prompts, max_new_tokens=n)   # compiles
        (got, stats), spans = _recorded(
            lambda: eng.generate(prompts, max_new_tokens=n), tmp_path)
        np.testing.assert_array_equal(got, want)
        assert stats["prefill_s"] > 0 and stats["decode_s_per_tok"] > 0

        names = [s[0] for s in spans]
        assert names.count("engine.generate") == 1
        # sampling runs inside the prefill and decode programs: no span of
        # its own
        for name, count in (("engine.init_caches", 1), ("engine.upload", 1),
                            ("engine.prefill", 1), ("engine.decode", n),
                            ("engine.pull", n), ("engine.sample", 0)):
            assert names.count(name) == count, name
        gen = next(s for s in spans if s[0] == "engine.generate")
        assert gen[3] == {"batch": 2, "new_tokens": n}
        steps = [s for s in spans if s[0] != "engine.generate"]
        kinds = {"engine.init_caches": "cpu", "engine.upload": "copy",
                 "engine.prefill": "device", "engine.pull": "copy",
                 "engine.decode": "device"}
        for name, start, end, stats in steps:
            assert gen[1] <= start <= end <= gen[2], name
            assert stats["segment"] == kinds[name], name
        for name in ("engine.decode", "engine.pull"):
            assert [s[3]["step"] for s in steps if s[0] == name] == \
                list(range(n))
        # the steps follow one another: no two overlap
        for a, b in zip(steps, steps[1:]):
            assert a[2] <= b[1], (a[0], b[0])

    def test_warm_generate_compiles_nothing(self):
        eng = ServingEngine(tiny_cfg(), ServeConfig(max_context=32, batch=2))
        prompts, n = _prompts(), 4
        want, _ = eng.generate(prompts, max_new_tokens=n)   # compiles

        def compiled():
            series = metrics.registry().snapshot().get(
                "jax_compiles_total", {}).get("series", {})
            return sum(series.values())

        try:
            metrics.enable(fresh=True)
            assert compiles.install()
            eng.generate((prompts + 1) % 256, max_new_tokens=n)
            got, _ = eng.generate(prompts, max_new_tokens=n)
            warm = compiled()
            # a new prompt length is a new prefill: the counter sees it
            eng.generate(prompts[:, :8], max_new_tokens=n)
            cold = compiled()
        finally:
            metrics.disable()
        assert warm == 0
        assert cold >= 1
        np.testing.assert_array_equal(got, want)

    def test_model_scopes_name_the_compiled_ops(self):
        eng = ServingEngine(tiny_cfg(), ServeConfig(max_context=32, batch=2))
        caches = eng.model.init_caches(2, 32)
        args = {"prefill": (eng._prefill, (
                    eng.params, jnp.asarray(_prompts()), caches)),
                "decode": (eng._decode, (
                    eng.params, jnp.zeros((2, 1), jnp.int32), caches,
                    jnp.full((2,), 12, jnp.int32)))}
        for program, (fn, a) in args.items():
            hlo = fn.lower(*a).compile().as_text()
            names = re.findall(r'op_name="([^"]*)"', hlo)
            scoped = [n for n in names if f"/{program}/" in n]
            assert scoped and not any(
                f"/{other}/" in n for n in names for other in args
                if other != program)
            for part in ("attention", "kv_update", "mlp", "lm_head"):
                assert any(f"/{part}/" in n for n in scoped), (program, part)


class TestExecutorSpans:
    def _spin(self, s):
        def job():
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < s:
                pass
        return job

    def test_job_spans_carry_the_trace_wait(self, tmp_path):
        trace = EventTrace(us_per_unit=1e6)
        svcs = [Service("a", 0.02, 0.02, self._spin(0.004)),
                Service("b", 0.03, 0.03, self._spin(0.006))]
        before = list(gc.callbacks)
        _, spans = _recorded(
            lambda: WallClockExecutor(svcs, trace=trace).run(0.2), tmp_path)
        assert gc.callbacks == before
        starts = [e for e in trace.events if e.kind == "start"]
        done = [e for e in trace.events if e.kind == "complete"]
        jobs = [s for s in spans if s[0] == "executor.job"]
        assert [s[3]["service"] for s in jobs] == [e.task for e in starts]
        # wait = start - due release, with release = complete - response
        for span, st, co in zip(jobs, starts, done):
            wait_us = (st.t - (co.t - dict(co.meta)["response_s"])) * 1e6
            assert span[3]["wait_us"] == pytest.approx(wait_us, abs=1e-2)
        run = [s for s in spans if s[0] == "executor.run"]
        assert len(run) == 1
        idle = [s for s in spans if s[0] == "executor.idle"]
        assert idle
        for s in jobs + idle:
            assert run[0][1] <= s[1] <= s[2] <= run[0][2]

    def test_gc_pauses_are_spans_and_the_hook_leaves(self, tmp_path):
        def collect():
            gc.collect()

        before = list(gc.callbacks)
        _, spans = _recorded(lambda: WallClockExecutor(
            [Service("a", 0.02, 0.02, collect)]).run(0.05), tmp_path)
        assert gc.callbacks == before
        pauses = [s for s in spans if s[0] == "host.gc"]
        # the job's full collections, beside any the allocator started
        full = [s for s in pauses if s[3]["generation"] == 2]
        jobs = [s for s in spans if s[0] == "executor.job"]
        assert full and jobs
        assert all(any(j[1] <= p[1] <= p[2] <= j[2] for j in jobs)
                   for p in full)

    def test_hook_leaves_when_a_job_raises(self):
        def boom():
            raise RuntimeError("job failed")

        before = list(gc.callbacks)
        with pytest.raises(RuntimeError):
            WallClockExecutor([Service("a", 0.02, 0.02, boom)]).run(0.05)
        assert gc.callbacks == before


class TestCompileCounter:
    def test_counts_each_compile_once_and_only_while_on(self):
        def compiled():
            return metrics.registry().value("jax_compiles_total",
                                            fun="jit(twice_plus_one)")

        def twice_plus_one(x):
            return 2 * x + 1

        x = jnp.arange(7.0)
        try:
            metrics.enable(fresh=True)
            assert compiles.install()
            f = jax.jit(twice_plus_one)
            f(x).block_until_ready()
            assert compiled() == 1.0
            f(x).block_until_ready()
            assert compiled() == 1.0
            secs = metrics.registry().snapshot()["jax_compile_seconds_total"]
            for stage in ("trace", "lower", "backend"):
                assert secs["series"][f"stage={stage}"] > 0, stage
            metrics.disable()
            jax.jit(twice_plus_one)(x + 1).block_until_ready()   # new jit
            metrics.enable()
            assert compiled() == 1.0
        finally:
            metrics.disable()

    def test_installs_one_listener(self):
        listeners = jax._src.monitoring.get_event_duration_listeners()
        try:
            metrics.enable(fresh=True)
            metrics.enable()
        finally:
            metrics.disable()
        after = jax._src.monitoring.get_event_duration_listeners()
        assert after.count(compiles._listener) == 1
        assert len(after) - len(listeners) <= 1
