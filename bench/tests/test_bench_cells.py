"""Each cell driven in-process on the CPU at a tiny scale: it agrees with
the reference, and it comes out not correct when the timed path is broken
underneath (the harness's look for a chip is skipped)."""
import sys
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import runner, spec  # noqa: E402

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


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_agrees_with_the_reference(workload, tmp_path):
    res = run(tiny(workload), tmp_path)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    names = {m["name"] for m in spec.resolve(workload).end_to_end}
    assert set(res["metrics"]) == names
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"missing_pairs", "extra_pairs",
                                  "repeated_pairs", "failed_joins"}
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        res["device"])


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
