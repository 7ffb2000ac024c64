"""Fused-pipeline identity tests (DESIGN.md §12).

``JoinPlan(pipeline_mode="fused")`` must be *bitwise* result-identical to
the staged chain — same pairs, same ORDER — for every registered filter
method on every predicate, including empty and degenerate candidate
frames; the on-device compaction kernels must match their oracle; and the
new ``JoinStats`` stage-time fields must round-trip through the service
envelope.
"""
import numpy as np
import pytest

from repro.core.join import (IntervalLists, april_trichotomy_rows,
                             fused_status_rows, linestring_trichotomy_rows,
                             within_trichotomy_rows)
from repro.datagen import make_dataset, make_linestrings
from repro.datagen.synthetic import PolygonDataset
from repro.spatial import PIPELINE_MODES, JoinPlan
from repro.spatial.filters import available_filters
from repro.spatial.fused import check_pipeline_mode
from repro.spatial.plan import JoinStats

N_ORDER = 6
METHODS = tuple(available_filters())
PREDICATES = ("intersects", "within", "selection", "linestring")


@pytest.fixture(scope="module")
def rs():
    return (make_dataset("T1", seed=71, count=80),
            make_dataset("T2", seed=72, count=100))


@pytest.fixture(scope="module")
def lines():
    return make_linestrings(seed=73, count=90)


def _run(R, S, mode, method, predicate, **kw):
    plan = JoinPlan(R, S, filter=method, n_order=N_ORDER,
                    pipeline_mode=mode, **kw)
    plan.build()
    return plan.execute(predicate)


# --- fused == staged, every method x every predicate ----------------------

@pytest.mark.parametrize("predicate", PREDICATES)
@pytest.mark.parametrize("method", METHODS)
def test_fused_identical_to_staged(rs, lines, method, predicate):
    """Bitwise identity (pairs AND order); where the staged chain rejects a
    method x predicate combination, fused must reject it identically."""
    R, S = rs
    kw = {}
    if predicate == "linestring":
        R, S, kw = lines, rs[1], {"r_kind": "line"}
    try:
        ref, ref_stats = _run(R, S, "staged", method, predicate, **kw)
    except Exception as e:
        with pytest.raises(type(e)):
            _run(R, S, "fused", method, predicate, **kw)
        return
    got, stats = _run(R, S, "fused", method, predicate, **kw)
    assert np.array_equal(ref, got), (method, predicate)
    assert stats.pipeline_mode == "fused"
    assert ref_stats.pipeline_mode == "staged"
    assert stats.n_candidates == ref_stats.n_candidates
    assert stats.n_true_hits == ref_stats.n_true_hits
    assert stats.n_indecisive == ref_stats.n_indecisive


@pytest.mark.parametrize("mbr_backend", ("numpy", "jnp"))
def test_fused_identity_across_mbr_backends(rs, mbr_backend):
    """The fused MBR stage keeps the candidate lane on device only for
    mbr_backend='jnp'; both routes are staged-identical."""
    R, S = rs
    ref, _ = _run(R, S, "staged", "april", "intersects",
                  mbr_backend=mbr_backend)
    got, _ = _run(R, S, "fused", "april", "intersects",
                  mbr_backend=mbr_backend)
    assert np.array_equal(ref, got)


def test_pipeline_mode_validation():
    assert set(PIPELINE_MODES) == {"staged", "fused"}
    check_pipeline_mode("fused")
    with pytest.raises(ValueError):
        check_pipeline_mode("streamed")
    with pytest.raises(ValueError):
        JoinPlan(make_dataset("T9", seed=1, count=4),
                 make_dataset("T9", seed=2, count=4),
                 pipeline_mode="streamed")


# --- wide interval lists through the fused status lane ---------------------

def _wide_lists(rng, sizes, unit=False):
    """IntervalLists over random sorted disjoint lists of the given sizes
    (intervals 1-3 cells long, 4 apart or more, in one id range; unit cells
    when ``unit``), and F lists that are subsets of them."""
    a, f = [], []
    for n in sizes:
        starts = 4 * np.sort(rng.choice(1 << 14, size=n, replace=False))
        ints = np.stack([starts, starts + rng.integers(1, 4, n)],
                        axis=1).astype(np.uint64)
        a.append(ints)
        f.append(ints[rng.random(n) < 0.5])
    off = [np.concatenate([[0], np.cumsum([len(x) for x in l])])
           for l in (a, f)]
    if unit:
        return IntervalLists.from_unit_cells(off[0], np.concatenate(
            [x[:, 0] for x in a]))
    return (IntervalLists.from_intervals(off[0], np.concatenate(a)),
            IntervalLists.from_intervals(off[1], np.concatenate(f)))


@pytest.mark.parametrize("predicate", ("intersects", "within", "linestring"))
def test_fused_identical_to_staged_wide_lists(predicate):
    """A row whose A list is wider than 1024 intervals takes the fused
    status lane's widest bucket; its verdicts equal the staged host
    drivers' row for row."""
    rng = np.random.default_rng(1400)
    xa, xf = _wide_lists(rng, [1, 3, 8, 20, 40, 5])
    ya, yf = _wide_lists(rng, [1500, 2, 30, 1100, 7])
    ri, si = np.meshgrid(np.arange(6), np.arange(5), indexing="ij")
    ri, si = ri.ravel(), si.ravel()
    if predicate == "intersects":
        want = april_trichotomy_rows(xa, xf, ya, yf, ri, si)
    elif predicate == "within":
        want = within_trichotomy_rows(xa, ya, yf, ri, si)
    else:
        xa, xf = _wide_lists(rng, [4, 60, 9, 300, 1, 25], unit=True), None
        want = linestring_trichotomy_rows(xa, ya, yf, ri, si)
    got = np.asarray(fused_status_rows(predicate, xa, xf, ya, yf, ri, si))
    np.testing.assert_array_equal(got, want)
    assert len(set(want[si == 0])) > 1      # the wide row decides both ways


# --- property: random polygon batches -------------------------------------

def _star(rng):
    """Random star polygon in [0.01, 0.99]^2 (possibly sliver-thin)."""
    nv = int(rng.integers(4, 17))
    cx, cy = rng.uniform(0.2, 0.8, 2)
    r = rng.uniform(0.01, 0.2)
    ang = np.sort(rng.uniform(0, 2 * np.pi, nv)) + np.linspace(0, 1e-4, nv)
    rad = r * (1 + 0.5 * rng.uniform(-1, 1, nv))
    pts = np.stack([cx + rad * np.cos(ang), cy + rad * np.sin(ang)], axis=1)
    return np.clip(pts, 0.01, 0.99)


def _batch(polys, name):
    V = max(len(p) for p in polys)
    verts = np.zeros((len(polys), V, 2))
    for i, p in enumerate(polys):
        verts[i, : len(p)] = p
    return PolygonDataset(name=name, verts=verts,
                          nverts=np.asarray([len(p) for p in polys],
                                            np.int64))


def _assert_property(pr, ps, method, predicate):
    """Fused == staged bitwise for ANY random polygon batch — frames where
    every pair is decided, none survive to refinement, or the candidate
    set is empty all arise from these draws."""
    R, S = _batch(pr, "hr"), _batch(ps, "hs")
    ref, _ = _run(R, S, "staged", method, predicate)
    got, _ = _run(R, S, "fused", method, predicate)
    assert np.array_equal(ref, got), (method, predicate)


@pytest.mark.parametrize("seed", range(10))
def test_fused_identity_random_batches(seed):
    """Seeded fallback of the hypothesis property below — always runs."""
    rng = np.random.default_rng(1000 + seed)
    pr = [_star(rng) for _ in range(int(rng.integers(1, 7)))]
    ps = [_star(rng) for _ in range(int(rng.integers(1, 7)))]
    method = ("april", "ri", "none")[seed % 3]
    _assert_property(pr, ps, method, ("intersects", "within")[seed % 2])


try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    pass
else:
    @st.composite
    def polygon(draw):
        seed = draw(st.integers(0, 2**31 - 1))
        return _star(np.random.default_rng(seed))

    @given(st.lists(polygon(), min_size=1, max_size=6),
           st.lists(polygon(), min_size=1, max_size=6),
           st.sampled_from(("april", "ri", "none")),
           st.sampled_from(("intersects", "within")))
    @settings(max_examples=25, deadline=None)
    def test_fused_identity_property(pr, ps, method, predicate):
        _assert_property(pr, ps, method, predicate)


# --- compaction kernels ---------------------------------------------------

def _masks():
    rng = np.random.default_rng(9)
    yield np.zeros(0, bool)
    yield np.zeros(1, bool)
    yield np.ones(1, bool)
    yield np.zeros(257, bool)
    yield np.ones(257, bool)
    yield rng.random(1) < 0.5
    yield rng.random(513) < 0.3
    yield rng.random(4096) < 0.7
    yield rng.random(5000) < 0.01


@pytest.mark.parametrize("backend", ("jnp", "pallas"))
def test_compact_mask_matches_oracle(backend):
    """Both compaction backends are bit-identical to the argsort oracle on
    empty, all-true, all-false, and random lanes of awkward lengths."""
    import jax.numpy as jnp

    from repro.kernels.compact import compact_mask
    from repro.kernels.compact.ref import compact_mask_ref

    for mask in _masks():
        m = jnp.asarray(mask)
        perm, count = compact_mask(m, backend=backend)
        perm_ref, count_ref = compact_mask_ref(m)
        assert int(count) == int(count_ref) == int(mask.sum()), len(mask)
        assert np.array_equal(np.asarray(perm), np.asarray(perm_ref)), \
            (backend, len(mask))
        # the contract downstream gathers rely on: a permutation with the
        # True indices front-packed ascending, False indices after, ascending
        k = int(count)
        assert np.array_equal(np.sort(np.asarray(perm)),
                              np.arange(len(mask)))
        assert np.array_equal(np.asarray(perm[:k]), np.flatnonzero(mask))
        assert np.array_equal(np.asarray(perm[k:]), np.flatnonzero(~mask))


# --- degenerate candidate frames through the fused chain ------------------

def _one(square, name):
    return PolygonDataset(name=name, verts=square[None],
                          nverts=np.asarray([4], np.int64))


@pytest.mark.parametrize("method", METHODS)
def test_fused_empty_and_degenerate_frames(method):
    """Empty candidate sets and single-pair frames survive the compaction
    kernels and the end-of-chain sync identically to staged."""
    sq = np.array([[0.1, 0.1], [0.2, 0.1], [0.2, 0.2], [0.1, 0.2]])
    near = _one(sq + 0.05, "b")          # overlapping -> one live pair
    far = _one(sq + 0.6, "c")            # disjoint MBRs -> empty frame
    for other in (near, far):
        ref, ref_st = _run(_one(sq, "a"), other, "staged", method,
                           "intersects")
        got, st = _run(_one(sq, "a"), other, "fused", method, "intersects")
        assert np.array_equal(ref, got), (method, other.name)
        assert st.n_results == ref_st.n_results
    assert _run(_one(sq, "a"), far, "fused", method, "intersects")[1] \
        .n_candidates == 0


# --- JoinStats stage-time envelope ----------------------------------------

def test_stats_stage_times_roundtrip(rs):
    R, S = rs
    plan = JoinPlan(R, S, filter="april", n_order=N_ORDER,
                    pipeline_mode="fused")
    plan.build()
    _, stats = plan.execute("intersects")
    times = stats.stage_times()
    assert set(times) == {"t_mbr", "t_filter", "t_refine", "t_sync",
                          "t_partition", "t_total"}
    assert times["t_partition"] == 0.0   # non-tiled run (§14)
    assert times["t_total"] == pytest.approx(
        times["t_mbr"] + times["t_filter"] + times["t_refine"]
        + times["t_sync"])
    d = stats.to_dict()
    back = JoinStats.from_dict(d)
    assert back.pipeline_mode == "fused"
    assert back.stage_times() == times
    assert d["t_sync"] == stats.t_sync


def test_service_reports_stage_times(rs):
    from repro.spatial import JoinService
    R, _ = rs
    svc = JoinService(method="april", n_order=N_ORDER,
                      pipeline_mode="fused")
    svc.register_dataset("d", R)
    q = R.verts[0, : R.nverts[0]]
    t = svc.submit("d", "selection", q)
    svc.drain()
    t.wait(10.0)
    lat = svc.latency_stats()
    assert set(lat["stage_times"]) >= {"t_mbr", "t_filter", "t_refine",
                                       "t_sync"}
    assert lat["stage_times"]["t_total"] > 0.0
