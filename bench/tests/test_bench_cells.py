"""Each cell driven in-process on the CPU at a tiny scale: it agrees with
the reference, and it comes out not correct when the timed path is broken
underneath (the harness's look for a chip is skipped)."""
import sys
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import box_within_kind  # noqa: E402
from harness import mixes, runner, spec  # noqa: E402

K = 0.05
WORKLOADS = [w["name"] for w in spec.benchmark()["workloads"]]


def tiny(workload: str) -> spec.Cell:
    cell = spec.resolve(workload)
    cell.config["k"] = K
    return cell


def run(cell, tmp_path, traced=False, seed=2**32 + 3):
    import jax
    return runner.run(cell, seed, 0.3, traced, time.perf_counter(),
                      jax.devices()[:1], tmp_path / "trace", lambda m: None)


def assert_agrees(res, cell):
    """A sound run of ``cell``: correct, its end-to-end metrics, and the
    checks its driver declares, each with a number and a limit."""
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    drv = mixes.make(cell.traffic["kind"], cell.config, cell.traffic, 0)
    assert list(res["checks"]) == list(drv.CHECKS)
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"}
        assert all(isinstance(c[k], (int, float)) for k in c)
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        res["device"])


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_agrees_with_the_reference(workload, tmp_path):
    cell = tiny(workload)
    assert_agrees(run(cell, tmp_path), cell)


def test_a_new_kind_is_held_to_its_declared_checks(tmp_path, monkeypatch):
    box_within_kind.install(monkeypatch, mixes)
    cell = box_within_kind.cell(spec)
    assert_agrees(run(cell, tmp_path), cell)


def test_a_new_kind_runs_traced_without_the_join_inputs(tmp_path,
                                                        monkeypatch):
    """Its set-up gives no ``build_s`` and its layer inputs none of the
    join's work counts."""
    box_within_kind.install(monkeypatch, mixes)
    monkeypatch.setattr(runner, "peaks_for",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
    cell = box_within_kind.cell(spec)
    res = run(cell, tmp_path, traced=True)
    assert res["correct"], res["checks"]
    assert res["metrics"]["within.queries"]["value"] == res["attempted"]
    assert res["device"]["window_s"] > 0


def test_traced_run_reads_its_layers(tmp_path, monkeypatch):
    monkeypatch.setattr(runner, "peaks_for",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
    res = run(tiny(WORKLOADS[0]), tmp_path, traced=True)
    assert res["correct"]
    m = res["metrics"]
    assert 0 <= m["join.indecisive_share"]["value"] <= 100
    assert m["join.host_rows"]["value"] >= 0
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def _half(pairs, plan):
    return pairs[: len(pairs) // 2]


def _altered(pairs, plan):
    pairs = pairs.copy()
    pairs[0, 1] = (pairs[0, 1] + 1) % len(plan.S)
    return pairs


def _unchanged(pairs, plan):
    return np.zeros((0, 2), np.int64)


@pytest.mark.parametrize("fault", [_half, _altered, _unchanged],
                         ids=["half_left_out", "answer_altered",
                              "state_unchanged"])
@pytest.mark.parametrize("workload", WORKLOADS)
def test_broken_timed_path_is_not_correct(workload, fault, tmp_path,
                                          monkeypatch):
    from repro.spatial import JoinPlan
    execute = JoinPlan.execute

    def broken(self, predicate="intersects"):
        pairs, stats = execute(self, predicate)
        return fault(pairs, self), stats

    monkeypatch.setattr(JoinPlan, "execute", broken)
    res = run(tiny(workload), tmp_path)
    assert not res["correct"], res["checks"]


def test_a_raising_join_is_failed_work(tmp_path, monkeypatch):
    from repro.spatial import JoinPlan
    execute = JoinPlan.execute
    calls = []

    def flaky(self, predicate="intersects"):
        calls.append(1)
        if len(calls) > 2:      # the warm join and one timed join pass
            raise RuntimeError("device lost")
        return execute(self, predicate)

    monkeypatch.setattr(JoinPlan, "execute", flaky)
    res = run(tiny(WORKLOADS[0]), tmp_path)
    assert res["failed"] == 1 and not res["correct"]
