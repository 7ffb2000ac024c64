"""The control of the comparison: the plain reference put in the
program's place in float32, one precision below the stated float64,
comes out not correct; in float64 it comes out correct."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import box_within_kind  # noqa: E402
import control  # noqa: E402
from harness import datagen, mixes, reference, spec  # noqa: E402

WORKLOADS = [w["name"] for w in spec.benchmark()["workloads"]]


def small(workload: str, k: float = 0.3) -> spec.Cell:
    cell = spec.resolve(workload)
    cell.config["k"] = k
    return cell


@pytest.mark.parametrize("workload", WORKLOADS)
@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_f32_control_fails(workload, seed):
    checks = control.control_checks(small(workload), seed)
    assert checks["extra_pairs"][0] + checks["missing_pairs"][0] > 0


@pytest.mark.parametrize("workload", WORKLOADS)
def test_exact_reference_in_the_programs_place_passes(workload):
    checks = control.control_checks(small(workload), 5, "exact")
    assert all(v <= lim for v, lim in checks.values())


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_control_of_a_new_kind_uses_its_own_data_and_reference(
        seed, monkeypatch):
    """A kind whose answer is not the intersects pair set: its exact
    reference in the program's place passes, its float32 one fails."""
    box_within_kind.install(monkeypatch, mixes)
    cell = box_within_kind.cell(spec)
    exact = control.control_checks(cell, seed, "exact")
    assert exact == {"wrong_within_pairs": (0, 0)}
    f32 = control.control_checks(cell, seed)
    assert list(f32) == ["wrong_within_pairs"]
    assert f32["wrong_within_pairs"][0] >= 256 // 2


def test_reference_agrees_with_a_per_pair_loop():
    from repro.core import geometry
    g = datagen.deployment(small("t1t3.join", 0.1).config, 0)
    (vr, nr, mr), (vs, ns, ms) = g["r"], g["s"]
    got = reference.pairs(g["r"], g["s"])
    want = [(i, j) for i, j in reference.mbr_pairs(mr, ms)
            if geometry.polygons_intersect(vr[i], nr[i], vs[j], ns[j])]
    assert reference.compare(got, np.array(want), len(ns)) == {
        "missing": 0, "extra": 0, "repeated": 0}


def test_touching_and_nested_polygons():
    sq = np.array([[0, 0], [1, 0], [1, 1], [0, 1]], float)
    cases = [(sq + [1, 0], True),       # shares an edge
             (sq + [1, 1], True),       # shares a corner
             (sq * 0.2 + 0.4, True),    # nested inside
             (sq + [1.01, 0], False)]   # apart
    for other, want in cases:
        v = np.stack([sq, other])
        n = np.array([4, 4])
        assert reference.intersects(v[:1], n[:1], v[1:], n[1:])[0] == want
        assert reference.intersects(v[1:], n[1:], v[:1], n[:1])[0] == want


@pytest.mark.parametrize("got,want", [
    ([[0, 1], [2, 3]], {"missing": 0, "extra": 0, "repeated": 0}),
    ([[0, 1]], {"missing": 1, "extra": 0, "repeated": 0}),
    ([[0, 1], [2, 3], [4, 0]], {"missing": 0, "extra": 1, "repeated": 0}),
    ([[2, 3], [0, 1], [2, 3]], {"missing": 0, "extra": 0, "repeated": 1}),
    (np.zeros((0, 2)), {"missing": 2, "extra": 0, "repeated": 0}),
])
def test_compare_counts(got, want):
    assert reference.compare(np.array(got), np.array([[0, 1], [2, 3]]),
                             5) == want
