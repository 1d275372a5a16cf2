"""One cell, driven through the program's own entry points.

Set-up: the weights of every model the cell serves, made on the device from
the seed; one ``ServingEngine`` per service, warmed on the cell's own shapes;
a few timed warm jobs per service, whose worst decode step becomes
``ServingTaskSpec.roofline_step_s``; admission of every service on one
``DynamicController``.  Window: ``WallClockExecutor.run`` over the admitted
services, each job one ``ServingEngine.generate`` on prompts drawn from the
seed.  Check: the plain reference, run once the program's state is freed, on
a sample of the finished jobs.
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import time
import traceback
import zlib
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import Model
from repro.runtime import (
    Service, ServingTaskSpec, WallClockExecutor, serving_task_to_rt,
)
from repro.sched import DynamicController, EventTrace
from repro.serving import ServeConfig, ServingEngine

import jobs as jobs_mod
import registry

WARM_JOBS = 3


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _tag(name: str) -> int:
    return zlib.crc32(name.encode())


def seed_key(seed: int):
    """A JAX key from any whole number (the seed may not fit 32 bits)."""
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


@dataclasses.dataclass
class ModelSpec:
    name: str
    arch: str
    family: object        # bench/families/<family>.py
    cost: object          # bench/cost/<family>.py
    dims: object
    dtype: str

    @classmethod
    def load(cls, name: str, entry: dict) -> "ModelSpec":
        fam = registry.family(entry["family"])
        return cls(name, entry["arch"], fam, registry.cost(entry["family"]),
                   fam.dims(entry["config"]), entry["dtype"])

    def weights(self, seed: int, dtype=None):
        return self.family.make_weights(
            self.dims, jax.random.fold_in(seed_key(seed), _tag(self.name)),
            jnp.dtype(dtype or self.dtype))

    def program_config(self):
        """The program's own configuration of this model, held to the
        published sizes."""
        cfg = get_config(self.arch)
        if cfg.dtype != self.dtype:
            cfg = dataclasses.replace(cfg, dtype=self.dtype)
        got = {k: getattr(cfg, k) for k in
               self.family.program_fields(self.dims)}
        want = self.family.program_fields(self.dims)
        if got != want:
            raise ValueError(f"{self.arch}: program config {got} differs "
                             f"from the published sizes {want}")
        return cfg


@dataclasses.dataclass
class ServiceRun:
    spec: dict            # the service's entry in the cell file
    model: ModelSpec
    pool: np.ndarray      # [n, batch, prompt_len] prompts drawn from the seed
    engine: Optional[ServingEngine] = None
    step_s: float = 0.0
    warm_job_s: float = 0.0     # the slowest warm job
    median_job_s: float = 0.0
    bound_ms: Optional[float] = None     # certified R-hat, None if refused
    count: int = 0
    done: list = dataclasses.field(default_factory=list)   # (pool idx, out)
    errors: int = 0       # jobs that raised, over the windows since fresh
    errors_before: int = 0   # ``errors`` as the last window began

    @property
    def name(self) -> str:
        return self.spec["name"]

    @property
    def period_s(self) -> float:
        return self.spec["period_ms"] / 1e3

    @property
    def deadline_s(self) -> float:
        return self.spec["deadline_ms"] / 1e3

    def job(self) -> None:
        i = self.count % len(self.pool)
        self.count += 1
        with jax.profiler.TraceAnnotation(f"job:{self.name}"):
            try:
                out, _ = self.engine.generate(
                    self.pool[i], max_new_tokens=self.spec["new_tokens"])
            except Exception:       # the window runs on; the job failed
                self.errors += 1
                log(f"job of {self.name} raised:\n{traceback.format_exc()}")
                return
        self.done.append((i, out))


class Cell:
    def __init__(self, name: str, seed: int, pool_jobs: int,
                 cell: Optional[dict] = None, config: Optional[dict] = None):
        self.name = name
        self.seed = seed
        self.cell = cell or registry.workload(name)
        self.config = config or registry.config(self.cell["config"])
        self.models = {m: ModelSpec.load(m, e)
                       for m, e in self.config["models"].items()}
        self.services = []
        for spec in self.cell["services"]:
            m = self.models[spec["model"]]
            rng = np.random.default_rng([seed, _tag(spec["name"])])
            pool = rng.integers(
                0, m.dims.vocab,
                (pool_jobs, spec["batch"], spec["prompt_len"]), np.int32)
            self.services.append(ServiceRun(spec, m, pool))
        self.params: dict = {}

    # ------------------------------------------------------------ set-up

    def build(self) -> None:
        """Weights on the device, one engine per service, every shape the
        window uses compiled and run once."""
        for m in self.models.values():
            if any(s.model is m for s in self.services):
                cfg = m.program_config()
                params = m.family.to_program(m.dims, m.weights(self.seed))
                want = jax.eval_shape(Model(cfg).init_params,
                                      jax.random.PRNGKey(0))
                if (jax.tree_util.tree_structure(params)
                        != jax.tree_util.tree_structure(want) or any(
                        (a.shape, a.dtype) != (b.shape, b.dtype)
                        for a, b in zip(jax.tree_util.tree_leaves(params),
                                        jax.tree_util.tree_leaves(want)))):
                    raise ValueError(f"{m.name}: weights do not fit the "
                                     f"program's parameter tree")
                self.params[m.name] = (cfg, params)
        for s in self.services:
            cfg, params = self.params[s.model.name]
            s.engine = ServingEngine(
                cfg, ServeConfig(max_context=s.spec["max_context"],
                                 batch=s.spec["batch"]), params=params)
            s.engine.generate(s.pool[0], max_new_tokens=s.spec["new_tokens"])

    def time_warm_jobs(self, n: int = WARM_JOBS) -> None:
        for s in self.services:
            steps, jobs = [], []
            for i in range(n):
                t0 = time.perf_counter()
                _, st = s.engine.generate(
                    s.pool[1 + i], max_new_tokens=s.spec["new_tokens"])
                jobs.append(time.perf_counter() - t0)
                steps.append(st["decode_s_per_tok"])
            s.step_s, s.warm_job_s = max(steps), max(jobs)
            s.median_job_s = float(np.median(jobs))
            log(f"warm {s.name}: job {jobs!r} s, decode step {steps!r} s")

    def admit(self, scale: float = 1.0) -> None:
        """Admit every service on one controller; periods and deadlines
        times ``scale`` (1 in a run, other values in the sweep)."""
        ctl = DynamicController(**self.config["controller"])
        for s in self.services:
            spec = ServingTaskSpec(
                name=s.name, arch_id=s.model.arch,
                period_ms=s.spec["period_ms"] * scale,
                deadline_ms=s.spec["deadline_ms"] * scale,
                batch=s.spec["batch"], seq_len=s.spec["prompt_len"],
                new_tokens=s.spec["new_tokens"], roofline_step_s=s.step_s,
                vocab=s.model.dims.vocab, dominant="memory_s")
            dec = ctl.admit(serving_task_to_rt(spec))
            s.bound_ms = dec.bounds[s.name] if dec.admitted else None
            if not dec.admitted:
                log(f"admission refused {s.name}: {dec.reason}")

    # ------------------------------------------------------------ window

    def window(self, seconds: float, scale: float = 1.0,
               fresh: bool = True) -> EventTrace:
        """Run the admitted services for ``seconds``.  With ``fresh`` false
        the window follows the last one: jobs go on through the prompt pool
        and the finished ones add to those of the last window."""
        trace = EventTrace(us_per_unit=1e6)
        live = []
        for s in self.services:
            if fresh:
                s.count, s.done, s.errors = 0, [], 0
            s.errors_before = s.errors
            if s.bound_ms is not None:
                live.append(Service(s.name, s.period_s * scale,
                                    s.deadline_s * scale, s.job))
        self.stats = WallClockExecutor(live, trace=trace).run(
            duration_s=seconds)
        return trace

    def tally(self, seconds: float) -> tuple[int, int]:
        """(attempted, failed) of the last window.  Attempted: jobs released
        in it.  Failed: those that missed their deadline, raised, or were
        still unfinished when their deadline passed inside the window, and
        every release of a service that admission refused."""
        attempted = failed = 0
        for s in self.services:
            st = self.stats.get(s.name)
            if st is None:
                n = jobs_mod.releases(s.period_s, seconds)
                attempted, failed = attempted + n, failed + n
                continue
            late = jobs_mod.overdue_unfinished(
                st["released"], st["completed"], s.period_s, s.deadline_s,
                seconds)
            attempted += st["released"]
            errors = s.errors - s.errors_before
            failed += st["missed"] + errors + late
            log(f"window {s.name}: released {st['released']} completed "
                f"{st['completed']} missed {st['missed']} errors {errors} "
                f"overdue-unfinished {late}; worst R "
                f"{st['worst_response_ms']!r} ms vs certified R-hat "
                f"{s.bound_ms!r} ms")
        return attempted, failed

    def verdict(self, found: dict) -> tuple[bool, dict]:
        """``correct`` and the numbers it compared, each beside its limit.
        Every service must have been admitted, raised nothing, and served
        tokens whose widest gap is within its limit."""
        compared = {f"{s.name}.gap": {"value": found[s.name]["gap"],
                                      "limit": s.spec["gap_limit"]}
                    for s in self.services if s.name in found}
        correct = (len(compared) == len(self.services)
                   and all(found[s.name]["jobs"] > 0 for s in self.services)
                   and all(v["value"] <= v["limit"]
                           for v in compared.values())
                   and not any(s.errors for s in self.services))
        return correct, compared

    def free_program(self) -> None:
        for s in self.services:
            s.engine = None
        self.params.clear()
        gc.collect()

    # ------------------------------------------------------------- check

    def check(self, jobs_per_service: int, control: bool = False) -> dict:
        """Widest gap by which a served token's logit lies below the
        reference's best, over a sample of finished jobs drawn from the
        seed.  With ``control`` also the gap of the token that the fp8
        control puts first, at the same positions."""
        rng = np.random.default_rng([self.seed, _tag("check")])
        out = {}
        for m in self.models.values():
            mine = [s for s in self.services if s.model is m]
            if not mine:
                continue
            w = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                       m.weights(self.seed))
            wq = m.family.quantize_fp8(w) if control else None
            for s in mine:
                n = min(jobs_per_service, len(s.done))
                pick = rng.choice(len(s.done), size=n, replace=False)
                gap = ctl_gap = 0.0
                tokens = 0
                for j in sorted(pick):
                    i, served = s.done[j]
                    g, c = _job_gaps(m, w, wq, s.pool[i], served)
                    gap, ctl_gap = max(gap, g), max(ctl_gap, c)
                    tokens += served.size
                out[s.name] = {"gap": gap, "jobs": n, "tokens": tokens}
                if control:
                    out[s.name]["control_gap"] = ctl_gap
            del w, wq
            gc.collect()
        return out


def _job_gaps(m: ModelSpec, w, wq, prompt, served) -> tuple[float, float]:
    n = served.shape[1]
    toks = jnp.asarray(np.concatenate([prompt, served[:, :n - 1]], 1))
    first = prompt.shape[1] - 1
    ref = m.family.forward(m.dims, w, toks, first, n)
    best = ref.max(-1)
    got = jnp.take_along_axis(ref, jnp.asarray(served)[..., None], -1)[..., 0]
    gap = float((best - got).max())
    if wq is None:
        return gap, 0.0
    low = m.family.forward(m.dims, wq, toks, first, n).argmax(-1)
    lowv = jnp.take_along_axis(ref, low[..., None], -1)[..., 0]
    return gap, float((best - lowv).max())
