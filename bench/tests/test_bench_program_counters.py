"""The readers of the program's own counters and spans: each reads a
finite value of at least 0 from a tiny traced run on the CPU, and
``None`` from ``JoinStats`` that carry no counters or spans (a program
without them)."""
import math
import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import runner, spec  # noqa: E402

READERS = ("join.filter_pad_factor", "join.filter_buckets", "join.h2d_mb",
           "join.d2h_mb", "join.mbr_frame_ms", "join.filter_host_ms")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    import jax
    cell = spec.resolve("t1t3.join")
    cell.config["k"] = 0.05
    peaks = runner.peaks_for
    runner.peaks_for = lambda kind: {"hbm_bytes_per_s": 819e9}
    try:
        return runner.run(cell, 2**32 + 5, 0.3, True, time.perf_counter(),
                          jax.devices()[:1],
                          tmp_path_factory.mktemp("trace"), lambda m: None)
    finally:
        runner.peaks_for = peaks


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_the_traced_run(traced, name):
    assert traced["correct"]
    value = traced["metrics"][name]["value"]
    assert math.isfinite(value) and value >= 0
    if name == "join.filter_pad_factor":
        assert value >= 1.0          # gathered bytes cover needed bytes
    if name in ("join.filter_buckets", "join.h2d_mb", "join.d2h_mb"):
        assert value > 0


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_without_program_counters(name):
    stats = [{"n_candidates": 10, "n_indecisive": 2,
              "extra": {"routed": {"filter_wide_rows_host": 0}}}]
    ctx = {"stats": stats, "units": 1, "filter_bytes": 800}
    assert spec.load_reader(name)(ctx) is None
    assert spec.load_reader(name)({"stats": [], "units": 0,
                                   "filter_bytes": 800}) is None


def test_filter_host_ms_leaves_out_the_device_bound_dispatch():
    spans = {"repro.join": 9.0, "repro.filter": 8.0,
             "repro.filter.plan": 0.001, "repro.filter.bucket": 7.5,
             "repro.filter.args": 0.002, "repro.filter.dispatch": 7.4}
    stats = [{"extra": {"spans_s": spans}}, {"extra": {"spans_s": spans}}]
    got = spec.load_reader("join.filter_host_ms")({"stats": stats})
    assert got == pytest.approx(3.0)
