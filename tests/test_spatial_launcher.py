"""End-to-end distributed spatial-join launcher: correctness vs the
single-process pipeline + partition-checkpoint resume."""
import numpy as np

from repro.datagen import make_dataset
from repro.launch.spatial_join import run_join
from repro.spatial import spatial_intersection_join


def _pairs_set(p):
    return set(map(tuple, np.asarray(p).tolist()))


def test_launcher_matches_pipeline(tmp_path):
    res, totals = run_join("T1", "T2", n_order=7, parts=2, seed=0,
                           count_r=60, count_s=90,
                           ckpt_dir=str(tmp_path / "ck"))
    R = make_dataset("T1", seed=0, count=60)
    S = make_dataset("T2", seed=1, count=90)
    ref, _ = spatial_intersection_join(R, S, method="none")
    assert _pairs_set(res) == _pairs_set(ref)
    assert totals["true_neg"] > 0

    # resume from checkpoint: all partitions done -> same results, no rework
    res2, _ = run_join("T1", "T2", n_order=7, parts=2, seed=0,
                       count_r=60, count_s=90,
                       ckpt_dir=str(tmp_path / "ck"))
    assert _pairs_set(res2) == _pairs_set(ref)


def test_launcher_adaptive_plan_matches_pipeline():
    # per-partition planning (DESIGN.md §13): no global prebuilt stores,
    # each partition picks its own config, results identical to the
    # refine-everything reference
    res, totals = run_join("T1", "T2", n_order=7, parts=2, seed=0,
                           count_r=60, count_s=90, plan_mode="adaptive")
    R = make_dataset("T1", seed=0, count=60)
    S = make_dataset("T2", seed=1, count=90)
    ref, _ = spatial_intersection_join(R, S, method="none")
    assert _pairs_set(res) == _pairs_set(ref)


def test_routed_counts_nest_into_the_enclosing_block():
    from repro.kernels import count_routed, note_routed
    with count_routed() as outer:
        note_routed("filter_wide_rows_host", 2)
        with count_routed() as inner:
            note_routed("refine_escalated_rows_host", 3)
        assert inner["refine_escalated_rows_host"] == 3
    assert outer["filter_wide_rows_host"] == 2
    assert outer["refine_escalated_rows_host"] == 3


def test_launcher_reports_routed_rows():
    # the mesh paths' guard-band escalations reach the launcher's counts
    from repro.kernels import ROUTED_KEYS
    _, totals = run_join("T1", "T2", n_order=7, parts=2, seed=0,
                         count_r=60, count_s=90, pipeline_mode="fused")
    assert set(totals["routed"]) == set(ROUTED_KEYS)
    assert all(v >= 0 for v in totals["routed"].values())
