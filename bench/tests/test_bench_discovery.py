"""Every configuration, cell and metric is a file of its own, found by name;
BENCHMARK.json agrees with them; a new one is found with no edit."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

import registry

BENCH = registry.BENCH
SPEC = registry.benchmark()
CODE = ("run.py", "harness.py", "registry.py", "tracefile.py", "jobs.py",
        "calibrate.py", "sweep.py")


def test_every_file_loads_and_is_well_named():
    for name in registry.names("configs"):
        cfg = registry.config(name)
        for mname, entry in cfg["models"].items():
            registry.check_name(mname)
            registry.family(entry["family"]).dims(entry["config"])
            registry.cost(entry["family"])
    for name in registry.names("workloads"):
        cell = registry.workload(name)
        registry.check_name(cell["config"])
        registry.check_name(cell["traffic"])
        assert cell["config"] in registry.names("configs")
        models = registry.config(cell["config"])["models"]
        for svc in cell["services"]:
            assert svc["model"] in models
            assert 0 < svc["prompt_len"] + svc["new_tokens"] <= \
                svc["max_context"]
            assert svc["deadline_ms"] <= svc["period_ms"]
            assert svc["gap_limit"] > 0
    for name in registry.names("metrics"):
        assert callable(registry.metric(name).read)


def test_benchmark_json_matches_the_files():
    allowed = {"command", "paths", "run_seconds", "configs", "workloads",
               "end_to_end", "per_layer"}
    assert set(SPEC) == allowed
    assert sorted(c["name"] for c in SPEC["configs"]) == \
        registry.names("configs")
    for c in SPEC["configs"]:
        assert Path(c["file"]) == Path("bench/configs") / f"{c['name']}.json"
        for k in c["reduced"]:
            registry.check_name(k)
    assert sorted(w["name"] for w in SPEC["workloads"]) == \
        registry.names("workloads")
    for w in SPEC["workloads"]:
        cell = registry.workload(w["name"])
        for k in ("config", "traffic", "chips", "why"):
            assert w[k] == cell[k], (w["name"], k)
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    for m in metrics:
        registry.check_name(m["name"])
        assert registry.UNIT_RE.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert sorted(m["name"] for m in metrics) == registry.names("metrics")
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])


def test_each_per_layer_metric_moves_a_metric_its_cells_report():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in e2e[m["moves"]].get("workloads", cells)


def test_harness_names_no_cell_config_or_metric():
    names = set(registry.names("configs")) | set(registry.names("workloads"))
    names |= set(registry.names("metrics"))
    for cell in registry.names("workloads"):
        names |= {s["model"] for s in registry.workload(cell)["services"]}
    for f in CODE:
        text = (BENCH / f).read_text()
        for n in names:
            pattern = rf"(?<![\w.-]){re.escape(n)}(?![\w-])"
            assert not re.search(pattern, text), (f, n)


def test_added_files_are_found_without_edits(tmp_path):
    """A configuration, a cell and a metric that exist only as new files."""
    for kind in ("configs", "workloads", "metrics"):
        (tmp_path / kind).mkdir()
    cfg = registry.config(registry.names("configs")[0])
    (tmp_path / "configs" / "new-model.json").write_text(json.dumps(cfg))
    cell = dict(registry.workload(registry.names("workloads")[0]),
                config="new-model", traffic="new-mix")
    (tmp_path / "workloads" / "new-model.new-mix.json").write_text(
        json.dumps(cell))
    (tmp_path / "metrics" / "jobs_seen.py").write_text(
        "def read(record):\n    return float(len(record['jobs'])) or None\n")
    assert registry.names("configs", tmp_path) == ["new-model"]
    assert registry.names("workloads", tmp_path) == ["new-model.new-mix"]
    got = registry.workload("new-model.new-mix", tmp_path)
    assert registry.config(got["config"], tmp_path) == cfg
    reader = registry.metric("jobs_seen", tmp_path)
    assert reader.read({"jobs": [1, 2]}) == 2.0
    assert reader.read({"jobs": []}) is None


def test_bad_names_are_refused():
    for bad in ("a b", "a/b", "-x", "", "x" * 65, "é"):
        with pytest.raises(ValueError):
            registry.check_name(bad)
    with pytest.raises(FileNotFoundError):
        registry.workload("no-such-cell")


def test_peaks_are_keyed_by_device_kind():
    v5e = registry.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    for kind in ("TPU v4", "cpu", ""):
        with pytest.raises(KeyError):
            registry.peaks(kind)


def test_run_refuses_a_cpu(tmp_path):
    cell = registry.names("workloads")[0]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", cell,
         "--seed", str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120, cwd=tmp_path)
    assert proc.returncode != 0
    assert "needs" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
