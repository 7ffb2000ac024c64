"""Batched refinement subsystem (DESIGN.md §7): every backend must be
verdict-identical to the per-pair f64 sequential reference on every
predicate, including boundary-touching, collinear-edge and shared-vertex
geometry; plus the ISSUE-3 boundary-touch regressions and the sharded
(distributed) refinement path."""
import numpy as np
import pytest

from repro.core import geometry
from repro.datagen import make_dataset, make_linestrings
from repro.datagen.synthetic import PolygonDataset
from repro.spatial import JoinPlan, refine
from repro.spatial.distributed import distributed_refine

BATCHED = ("numpy", "jnp", "pallas")


@pytest.fixture(scope="module")
def rs():
    return (make_dataset("T1", seed=31, count=80),
            make_dataset("T10", seed=32, count=50))


@pytest.fixture(scope="module")
def poly_pairs(rs):
    R, S = rs
    return JoinPlan(R, S, filter="none").candidates("intersects")


# ---------------------------------------------------------------- identity

@pytest.mark.parametrize("backend", BATCHED)
def test_intersects_verdict_identical(rs, poly_pairs, backend):
    R, S = rs
    pairs = poly_pairs if backend != "pallas" else poly_pairs[:64]
    want = refine.refine_pairs_seq(R, S, pairs)
    got = refine.refine_pairs(R, S, pairs, backend=backend)
    assert want.sum() > 0 and (~want).sum() > 0
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("backend", BATCHED)
def test_within_verdict_identical(rs, poly_pairs, backend):
    R, S = rs
    pairs = poly_pairs if backend != "pallas" else poly_pairs[:64]
    want = refine.refine_within_pairs_seq(R, S, pairs)
    got = refine.refine_within_pairs(R, S, pairs, backend=backend)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("backend", BATCHED)
def test_linestring_verdict_identical(rs, backend):
    _, S = rs
    L = make_linestrings(seed=33, count=120)
    pairs = JoinPlan(L, S, filter="none",
                     r_kind="line").candidates("linestring")
    if backend == "pallas":
        pairs = pairs[:64]
    want = refine.refine_line_poly_pairs_seq(L, S, pairs)
    got = refine.refine_line_poly_pairs(L, S, pairs, backend=backend)
    assert want.sum() > 0
    np.testing.assert_array_equal(got, want)


def test_selection_dispatch_matches_intersects(rs, poly_pairs):
    R, S = rs
    np.testing.assert_array_equal(
        refine.refine(R, S, poly_pairs, predicate="selection"),
        refine.refine_pairs_seq(R, S, poly_pairs))


def test_unknown_backend_rejected(rs):
    R, S = rs
    with pytest.raises(ValueError, match="refine backend"):
        refine.refine_pairs(R, S, np.zeros((1, 2), np.int64), backend="tpu")


# ----------------------------------------------- boundary-touch geometry

def _ds(verts_list):
    V = max(len(v) for v in verts_list)
    verts = np.zeros((len(verts_list), V, 2))
    nv = np.zeros(len(verts_list), np.int64)
    for i, v in enumerate(verts_list):
        verts[i, : len(v)] = v
        nv[i] = len(v)
    return PolygonDataset(name="fixture", verts=verts, nverts=nv)


def test_touchy_geometry_all_backends():
    """Shared-vertex, collinear-shared-edge, exact-on-edge and containment
    contacts: batched backends agree with the sequential oracle."""
    sq = np.array([[0., 0.], [4., 0.], [4., 4.], [0., 4.]])
    R = _ds([
        sq + np.array([4.0, 0.0]),          # shares the x=4 edge
        sq + np.array([4.0, 4.0]),          # shares only the corner (4,4)
        np.array([[2., 4.], [3., 3.], [1., 3.]]),    # vertex on top edge
        np.array([[1., 1.], [3., 1.], [2., 3.]]),    # strictly inside
        sq,                                  # identical polygon
        sq + np.array([10., 10.]),           # disjoint
        np.array([[-1., -1.], [5., -1.], [5., 5.], [-1., 5.]]),  # contains
    ])
    S = _ds([sq] * len(R))
    pairs = np.stack([np.arange(len(R)), np.arange(len(R))], axis=1)
    want = refine.refine_pairs_seq(R, S, pairs)
    np.testing.assert_array_equal(
        want, [True, True, True, True, True, False, True])
    for backend in BATCHED:
        got = refine.refine_pairs(R, S, pairs, backend=backend)
        np.testing.assert_array_equal(got, want, err_msg=backend)
    # within with boundary contact: inner triangle touching the top edge
    w_want = refine.refine_within_pairs_seq(R, S, pairs)
    assert bool(w_want[2]) and bool(w_want[4])   # touching + identical
    for backend in BATCHED:
        got = refine.refine_within_pairs(R, S, pairs, backend=backend)
        np.testing.assert_array_equal(got, w_want, err_msg=backend)


# ------------------------------------------------- ISSUE-3 regressions

def test_regression_touching_containment_first_vertex():
    """A polygon whose first vertex is snapped onto the other's (diagonal)
    boundary used to refine False: the sweep sees no crossing and the old
    first-vertex crossing-parity fallback misclassified the snapped vertex
    outside. The exact-rational truth on the stored floats is True."""
    from repro.datagen.fixtures import SNAPPED_HOST, SNAPPED_TRI
    assert geometry.polygons_intersect(SNAPPED_TRI, 3, SNAPPED_HOST, 8)
    R, S = _ds([SNAPPED_TRI]), _ds([SNAPPED_HOST])
    pairs = np.asarray([[0, 0]], np.int64)
    for backend in ("sequential",) + BATCHED:
        assert refine.refine_pairs(R, S, pairs, backend=backend)[0], backend


def test_regression_within_concave_container():
    """'r within s' with a concave container: the old on-boundary fallback
    nudged vertices toward the container centroid, which lies OUTSIDE a
    C-shaped container — a false negative for a touching inner polygon."""
    from repro.datagen.fixtures import CSHAPE, CSHAPE_INNER
    cshape, inner = CSHAPE, CSHAPE_INNER               # vertex on y=2 edge
    assert geometry.polygon_within(inner, 3, cshape, 8)
    # convex containers must keep working
    sq = np.array([[0., 0.], [10., 0.], [10., 10.], [0., 10.]])
    top = np.array([[6., 10.], [7., 8.5], [5., 8.5]])
    assert geometry.polygon_within(top, 3, sq, 4)
    # and a genuinely outside polygon must not be 'within'
    out = inner + np.array([0.0, 2.5])                 # pokes into the cavity
    assert not geometry.polygon_within(out, 3, cshape, 8)
    R, S = _ds([inner]), _ds([cshape])
    pairs = np.asarray([[0, 0]], np.int64)
    for backend in ("sequential",) + BATCHED:
        assert refine.refine_within_pairs(R, S, pairs,
                                          backend=backend)[0], backend


def test_pallas_short_edge_guard_band():
    """f64 -> f32 casting perturbs coordinates by ~eps32 * |coord| — an
    absolute error the old edge-length-relative guard band missed for
    short edges away from the origin. Tiny near-touching polygons at
    O(1) coordinates must still be verdict-identical (borderline pairs
    escalate to host)."""
    rng = np.random.default_rng(19)
    polys_r, polys_s = [], []
    for i in range(24):
        c = rng.uniform(0.3, 0.7, 2)
        r1, r2 = rng.uniform(2e-5, 8e-5, 2)

        def star(cc, r, nv):
            ang = np.sort(rng.uniform(0, 2 * np.pi, nv))
            return np.stack([cc[0] + r * np.cos(ang),
                             cc[1] + r * np.sin(ang)], axis=1)

        ps = star(c, r1, 8)
        pr = star(c + rng.uniform(-1, 1, 2) * (r1 + r2) * 0.8, r2, 7)
        if i % 2 == 0:      # snap a vertex onto an edge: exact touching
            t = rng.uniform(0, 1)
            pr[0] = ps[0] + t * (ps[1] - ps[0])
        polys_r.append(pr)
        polys_s.append(ps)
    R, S = _ds(polys_r), _ds(polys_s)
    pairs = np.stack([np.arange(len(R)), np.arange(len(R))], axis=1)
    want = refine.refine_pairs_seq(R, S, pairs)
    got = refine.refine_pairs(R, S, pairs, backend="pallas")
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        refine.refine_within_pairs(R, S, pairs, backend="pallas"),
        refine.refine_within_pairs_seq(R, S, pairs))


def test_regression_jnp_fma_guard_band():
    """XLA contracts mul+add into FMAs below HLO (optimization_barrier does
    not survive to LLVM), flipping a near-zero orientation sign on this
    fuzz-found snapped-vertex pair: the jitted jnp within-verdict disagreed
    with the sequential oracle. The guard band must escalate it to host."""
    va = np.array([
        [0.46821126201099456, 0.33001897689418036],
        [0.4595537937791133, 0.3350787644582686],
        [0.4592356227004228, 0.3329649341949457],
        [0.4596606610281497, 0.33099007529253766],
        [0.45616671890794774, 0.33252371036844647],
        [0.45623553878792783, 0.33048644467627664],
        [0.45969407452675615, 0.32471573049690555],
        [0.4609399563810834, 0.3250079025220754],
        [0.4717620978321982, 0.3274392233419345],
        [0.4626992907961244, 0.324031668283713],
        [0.46705223951997354, 0.32491571012657894],
        [0.46662147259952713, 0.3273967831499829]])
    vb = np.array([
        [0.4752340142333326, 0.3327771686923501],
        [0.47062455687358307, 0.33128636458924227],
        [0.468976987931185, 0.3401287235421079],
        [0.4621100503439218, 0.33613973562982113],
        [0.458980197448991, 0.3379977083450747],
        [0.45152906086282973, 0.33208269891216996],
        [0.4627947747182639, 0.3206307916141646],
        [0.4686857145345563, 0.32272521209315136],
        [0.46794202990619516, 0.325202662712839],
        [0.46984918890693217, 0.32449819535518454]])
    R, S = _ds([va]), _ds([vb])
    pairs = np.asarray([[0, 0]], np.int64)
    want = refine.refine_within_pairs_seq(R, S, pairs)
    for backend in BATCHED:
        got = refine.refine_within_pairs(R, S, pairs, backend=backend)
        np.testing.assert_array_equal(got, want, err_msg=backend)
    got, _ = distributed_refine(R, S, pairs, predicate="within")
    np.testing.assert_array_equal(got, want, err_msg="distributed")


# ------------------------------------------------------ plan + sharded

def test_joinplan_refine_backend_wiring(rs):
    R, S = rs
    ref = None
    for rb in ("sequential", "numpy", "jnp"):
        plan = JoinPlan(R, S, filter="april", n_order=7, refine_backend=rb)
        res, stats = plan.build().execute("intersects")
        assert stats.refine_backend == rb
        assert rb in stats.row()
        key = set(map(tuple, res.tolist()))
        ref = key if ref is None else ref
        assert key == ref, rb
    with pytest.raises(ValueError, match="refine backend"):
        JoinPlan(R, S, refine_backend="bogus")


def test_distributed_refine_matches_host(rs, poly_pairs):
    R, S = rs
    want = refine.refine_pairs(R, S, poly_pairs)
    got, counts = distributed_refine(R, S, poly_pairs)
    np.testing.assert_array_equal(got, want)
    assert counts["refined_true"] == int(want.sum())
    w_want = refine.refine_within_pairs(R, S, poly_pairs)
    w_got, _ = distributed_refine(R, S, poly_pairs, predicate="within")
    np.testing.assert_array_equal(w_got, w_want)


@pytest.mark.parametrize("predicate", ["intersects", "within"])
def test_distributed_refine_width_classes(rs, poly_pairs, monkeypatch,
                                          predicate):
    """With chunks small enough for the width classes to fill them, each
    class gathers its own vertex widths — narrower than the dataset's —
    and the verdicts stay identical to the host."""
    from repro.spatial import distributed
    R, S = rs
    monkeypatch.setattr(refine, "_DEVICE_CHUNK_ELEMS", 1 << 12)
    widths = set()
    make_step = distributed._refine_shard_step

    def recording_step(*a):
        step = make_step(*a)

        def run(vr, nr, vs, ns, *rest):
            widths.add((vr.shape[1], vs.shape[1]))
            return step(vr, nr, vs, ns, *rest)
        return run

    monkeypatch.setattr(distributed, "_refine_shard_step", recording_step)
    got, _ = distributed_refine(R, S, poly_pairs, predicate=predicate)
    want = refine.refine(R, S, poly_pairs, predicate=predicate)
    np.testing.assert_array_equal(got, want)
    full = (R.verts.shape[1], S.verts.shape[1])
    assert len(widths) > 1 and full in widths
    assert all(a <= full[0] and b <= full[1] for a, b in widths)


def test_distributed_refine_linestring(rs):
    _, S = rs
    L = make_linestrings(seed=34, count=60)
    pairs = JoinPlan(L, S, filter="none",
                     r_kind="line").candidates("linestring")
    want = refine.refine_line_poly_pairs(L, S, pairs)
    got, _ = distributed_refine(L, S, pairs, predicate="linestring")
    np.testing.assert_array_equal(got, want)


def test_launcher_sharded_refine_matches_host_refine():
    from repro.launch.spatial_join import run_join
    res_a, _ = run_join("T1", "T2", n_order=7, parts=2, seed=3,
                        count_r=40, count_s=60, refine_backend="numpy")
    res_b, _ = run_join("T1", "T2", n_order=7, parts=2, seed=3,
                        count_r=40, count_s=60, refine_backend="jnp")
    assert (set(map(tuple, np.asarray(res_a).tolist()))
            == set(map(tuple, np.asarray(res_b).tolist())))
