"""The benchmark's own generator: deterministic per seed, and true to its
spec table at the scale a configuration states."""
import math
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import datagen, spec  # noqa: E402


@pytest.mark.parametrize("name", ["T1", "T2", "T3"])
def test_same_seed_same_layer_other_seed_other_layer(name):
    side = datagen.scaled(name, 0.5)
    v1, n1 = datagen.layer(side, 2**33 + 5)
    v2, n2 = datagen.layer(side, 2**33 + 5)
    v3, _ = datagen.layer(side, 2**33 + 6)
    assert np.array_equal(v1, v2) and np.array_equal(n1, n2)
    assert not np.array_equal(v1, v3)


@pytest.mark.parametrize("name", ["T1", "T2", "T3"])
def test_layer_matches_its_spec(name):
    count, nv, radius, _ = datagen.SPECS[name]
    k = 4.0
    side = datagen.scaled(name, k)
    verts, nverts = datagen.layer(side, 0)
    assert len(nverts) == side["count"] == round(count * k)
    assert abs(nverts.mean() - nv) < 4 * math.sqrt(nv / len(nverts)) + 0.5
    assert nverts.min() >= 4
    m = datagen.mbrs(verts, nverts)
    assert (m[:, :2] > 0).all() and (m[:, 2:] < 1).all()
    # the median half-extent tracks the scaled radius (log-normal spread)
    half = np.median((m[:, 2] - m[:, 0]) / 2)
    assert 0.5 * side["mean_radius"] < half < 1.5 * side["mean_radius"]
    assert side["mean_radius"] == pytest.approx(radius / math.sqrt(k))


def test_chunks_are_seeded_apart():
    side = dict(datagen.scaled("T2", 1.0), count=datagen.CHUNK + 10)
    verts, nverts = datagen.layer(side, 1)
    assert len(nverts) == datagen.CHUNK + 10
    assert not np.array_equal(verts[:10], verts[datagen.CHUNK:])


@pytest.mark.parametrize("k,order", [(1, 8), (2.5, 9), (5, 9), (10, 10),
                                     (40, 11), (0.05, 6)])
def test_scaled_order(k, order):
    assert datagen.scaled_order(k) == order


@pytest.mark.parametrize("entry", spec.benchmark()["configs"],
                         ids=lambda c: c["name"])
def test_config_files_state_the_scaled_spec(entry):
    cfg = spec.load_json(spec.ROOT / entry["file"])
    k = cfg["k"]
    for side in ("r", "s"):
        name = cfg["layers"][side]["spec"]
        count = datagen.scaled(name, k)["count"]
        assert f"{count:,}" in cfg["reduced"]["k"]
        assert f"{datagen.SPECS[name][0]:,}" in cfg["reduced"]["k"]
    assert f"= {datagen.scaled_order(k)}." in cfg["reduced"]["k"]
    assert set(entry["reduced"]) == set(cfg["reduced"])


def _config(s_spec="T2", k=0.5):
    return {"k": k, "data_seed": 3,
            "layers": {"r": {"spec": "T1", "seed_offset": 0},
                       "s": {"spec": s_spec, "seed_offset": 1}}}


@pytest.mark.parametrize("s_spec", ["T2", "T3"])
def test_near_misses_do_not_intersect_and_fool_float32(s_spec):
    from harness import reference
    g = datagen.deployment(_config(s_spec), 11)
    near = g["near"]
    assert len(near) == max(1, round(datagen.NEAR_SHARE * len(g["r"][1])))
    assert len(set(near[:, 0].tolist())) == len(near)
    (vr, nr, mr), (vs, ns, ms) = g["r"], g["s"]
    i, j = near[:, 0], near[:, 1]
    assert ((mr[i, :2] <= ms[j, 2:]) & (ms[j, :2] <= mr[i, 2:])).all()
    exact = reference.intersects(vr[i], nr[i], vs[j], ns[j])
    f32 = reference.intersects(vr[i], nr[i], vs[j], ns[j], np.float32)
    assert not exact.any()
    assert f32.all()


def test_seed_moves_vertices_below_the_jitter_bound():
    a = datagen.deployment(_config(), 2**31 + 9)
    b = datagen.deployment(_config(), 2**31 + 9)
    c = datagen.deployment(_config(), 2**31 + 10)
    for side in ("r", "s"):
        assert np.array_equal(a[side][0], b[side][0])
        assert not np.array_equal(a[side][0], c[side][0])
        assert np.abs(a[side][0] - c[side][0]).max() <= 2 * datagen.SEED_JITTER
        assert np.array_equal(a[side][1], c[side][1])
    assert np.array_equal(a["near"], c["near"])
