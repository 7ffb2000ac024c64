"""Every entry of BENCHMARK.json resolves its files by name, and every
name and unit keeps to the benchmark's character rules."""
import json
import subprocess
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import mixes, spec  # noqa: E402

BM = spec.benchmark()
METRIC_KEYS = {"name", "unit", "better", "source", "workloads"}


def test_top_level_keys_and_command():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["command"] == ["python3", "bench/run.py"]
    assert BM["paths"] == ["bench"]
    assert 1 <= BM["run_seconds"] <= 51
    assert len(json.dumps(BM)) < 64 * 1024


@pytest.mark.parametrize("w", BM["workloads"], ids=lambda w: w["name"])
def test_workload_resolves(w):
    cell = spec.resolve(w["name"])
    assert cell.chips in (1, 4)
    assert mixes.kind_path(cell.traffic["kind"]).is_file()
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(cell.readers[m["name"]])
        moved = [e for e in cell.end_to_end if e["name"] == m["moves"]]
        assert moved, f"{m['name']} moves a metric {w['name']} lacks"
    assert 1 <= len(w["why"]) <= 200


@pytest.mark.parametrize("c", BM["configs"], ids=lambda c: c["name"])
def test_config_entry(c):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert c["file"].startswith("bench/configs/")
    assert (spec.ROOT / c["file"]).is_file()
    assert any(w["config"] == c["name"] for w in BM["workloads"])
    for key in c["reduced"]:
        assert spec.NAME_RE.match(key)
        assert not key.endswith(("_dim", "_rank"))


def test_names_units_and_sources():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BM[group]:
            assert spec.NAME_RE.match(e["name"]), e["name"]
            names.append((group, e["name"]))
    assert len(set(n for _, n in names)) == len(names)
    for w in BM["workloads"]:
        assert spec.NAME_RE.match(w["config"])
        assert spec.NAME_RE.match(w["traffic"])
    cells = {w["name"] for w in BM["workloads"]}
    for m in BM["end_to_end"]:
        assert set(m) <= METRIC_KEYS | {"bound"}
        assert spec.UNIT_RE.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    for m in BM["per_layer"]:
        assert set(m) <= METRIC_KEYS | {"layer", "moves"}
        assert spec.UNIT_RE.match(m["unit"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in {e["name"] for e in BM["end_to_end"]}
        assert set(m.get("workloads", cells)) <= cells
        assert spec.metric_path(m["name"]).is_file()
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_every_bench_file_name_is_made_of_name_characters():
    for p in spec.BENCH.rglob("*"):
        if "__pycache__" in p.parts or p.is_dir():
            continue
        rel = p.relative_to(spec.ROOT).as_posix()
        assert all(spec.NAME_RE.match(part) for part in rel.split("/")), rel


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        spec.resolve("no.such.cell")


@pytest.mark.parametrize("kind", ["no_such_kind", "../run", "batch.join"])
def test_unknown_traffic_kind_is_an_error(kind):
    with pytest.raises(ValueError):
        mixes.make(kind, {}, {}, 0)


def _run(cwd, env_extra=None):
    import os
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "t1t3.join",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_non_zero_without_a_tpu():
    p = _run(spec.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_run_exits_non_zero_without_the_program(tmp_path):
    import shutil
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
