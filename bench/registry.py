"""Finds the benchmark's pieces by name.  Nothing here names a cell, a
configuration or a metric: each lives in a file of its own.

  bench/configs/<config>.json     models served (published config keys,
                                  dtype, source) and controller settings
  bench/workloads/<cell>.json     configuration, traffic, chips, why and
                                  the services of one cell
  bench/metrics/<metric>.py       ``read(record)`` of one metric
  bench/families/<family>.py      weights, adapter and plain reference
  bench/cost/<family>.py          model FLOPs and bytes per call
  bench/peaks.json                chip peaks keyed by ``device_kind``
"""
from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def check_name(name: str) -> str:
    if not NAME_RE.match(name):
        raise ValueError(f"bad name {name!r}")
    return name


def _json(kind: str, name: str, root: Path = BENCH) -> dict:
    path = root / kind / f"{check_name(name)}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    return json.loads(path.read_text())


def _module(kind: str, name: str, root: Path = BENCH):
    path = root / kind / f"{check_name(name)}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module named {name!r} ({path})")
    key = f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}"
    mod = sys.modules.get(key)
    if mod is not None and getattr(mod, "__file__", None) == str(path):
        return mod
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def names(kind: str, root: Path = BENCH) -> list[str]:
    suffix = ".py" if kind == "metrics" else ".json"
    return sorted(p.name[:-len(suffix)] for p in (root / kind).glob(
        f"*{suffix}") if not p.name.startswith("_"))


def workload(name: str, root: Path = BENCH) -> dict:
    return _json("workloads", name, root)


def config(name: str, root: Path = BENCH) -> dict:
    return _json("configs", name, root)


def metric(name: str, root: Path = BENCH):
    return _module("metrics", name, root)


def family(name: str, root: Path = BENCH):
    return _module("families", name, root)


def cost(name: str, root: Path = BENCH):
    return _module("cost", name, root)


def peaks(kind: str, root: Path = BENCH) -> dict:
    """Peaks of one chip.  A kind that is not in the table is an error:
    there is no default."""
    table = json.loads((root / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device_kind {kind!r} in peaks.json")
    return table[kind]


def benchmark(root: Path = BENCH) -> dict:
    return json.loads((root.parent / "BENCHMARK.json").read_text())
