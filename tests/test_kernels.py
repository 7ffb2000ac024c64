"""Pallas kernel validation (interpret=True): shape/dtype sweeps vs the
pure-jnp oracles, plus integration against the core implementation."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import geometry
from repro.core.april import build_april
from repro.core.join import (batch_overlap_jnp, batch_overlap_np,
                             interval_join_pair, pack_lists)
from repro.datagen import make_dataset
from repro.kernels.april_attention.ops import april_attention, build_block_intervals
from repro.kernels.april_attention.ref import april_attention_ref, dense_mask
from repro.kernels.interval_join.ops import batch_interval_overlap
from repro.kernels.interval_join.ref import interval_overlap_ref
from repro.kernels.refine.ops import batch_edges_intersect
from repro.kernels.refine.ref import edges_intersect_ref
from repro.kernels.ri_and.ops import (batch_aligned_and, pack_bits_u32,
                                      xor_mask_words)
from repro.kernels.ri_and.ref import aligned_and_ref


# ---------------------------------------------------------------- interval_join

def _random_interval_batch(rng, B, I, J, spread=10_000):
    I32_MAX = np.iinfo(np.int32).max
    xs = np.full((B, I), I32_MAX, np.int32); xl = xs.copy()
    ys = np.full((B, J), I32_MAX, np.int32); yl = ys.copy()
    nx = rng.integers(0, I + 1, B).astype(np.int32)
    ny = rng.integers(0, J + 1, B).astype(np.int32)
    for b in range(B):
        if nx[b]:
            p = np.sort(rng.choice(spread, size=2 * nx[b], replace=False))
            xs[b, :nx[b]] = p[0::2]; xl[b, :nx[b]] = p[1::2] - 1
        if ny[b]:
            p = np.sort(rng.choice(spread, size=2 * ny[b], replace=False))
            ys[b, :ny[b]] = p[0::2]; yl[b, :ny[b]] = p[1::2] - 1
    return xs, xl, nx, ys, yl, ny


@pytest.mark.parametrize("B,I,J", [(5, 3, 4), (16, 64, 64), (9, 17, 130),
                                   (8, 128, 256), (3, 1, 1), (7, 40, 3),
                                   (6, 3, 40), (4, 32, 1024)])
def test_interval_join_kernel_sweep(B, I, J):
    """The kernel, the jnp rank count and the host pass agree with the
    oracle; Wx != Wy both ways, up to a 1024-wide y list."""
    rng = np.random.default_rng(B * 1000 + I + J)
    xs, xl, nx, ys, yl, ny = _random_interval_batch(rng, B, I, J)
    got = np.asarray(batch_interval_overlap(xs, xl, nx, ys, yl, ny,
                                            interpret=True))
    want = np.asarray(interval_overlap_ref(
        jnp.asarray(xs), jnp.asarray(xl), jnp.asarray(nx),
        jnp.asarray(ys), jnp.asarray(yl), jnp.asarray(ny)))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        np.asarray(batch_overlap_jnp(xs, xl, nx, ys, yl, ny)), want)
    np.testing.assert_array_equal(batch_overlap_np(xs, xl, nx, ys, yl, ny),
                                  want)


I32_MIN, I32_MAX = np.iinfo(np.int32).min, np.iinfo(np.int32).max

#: hand-made rows, ``(x intervals, y intervals, overlap)`` each, inclusive
#: lasts; a case's rows are padded to 8 with all-padding rows (count 0)
OVERLAP_EDGES = {
    "x_empty": [([], [(0, 9)], False), ([(3, 4)], [(0, 9)], True)],
    "y_empty": [([(0, 9)], [], False), ([(0, 9), (20, 29)], [], False)],
    "xs_touches_yl": [([(10, 20)], [(5, 10)], True),
                      ([(10, 20)], [(5, 9), (21, 30)], False)],
    "ys_touches_xl": [([(10, 20)], [(20, 30)], True),
                      ([(0, 3), (10, 19)], [(20, 30)], False)],
    "near_int32_min": [([(I32_MIN, I32_MIN)], [(I32_MIN, I32_MIN + 1)], True),
                       ([(I32_MIN + 2, I32_MIN + 3)],
                        [(I32_MIN, I32_MIN + 1)], False)],
    "near_int32_max": [([(I32_MAX - 1, I32_MAX - 1)],
                        [(I32_MAX - 2, I32_MAX - 1)], True),
                       ([(I32_MAX - 1, I32_MAX - 1)],
                        [(I32_MAX - 4, I32_MAX - 2)], False),
                       ([(0, 5), (I32_MAX - 3, I32_MAX - 2)],
                        [(I32_MAX - 1, I32_MAX - 1)], False)],
    "wide_y_gaps": [([(1001, 1001)], [(3 * j, 3 * j + 1)
                                      for j in range(600)], False),
                    ([(1000, 1000)], [(3 * j, 3 * j + 1)
                                      for j in range(600)], True)],
    "all_padding": [],
}


@pytest.mark.parametrize("case", sorted(OVERLAP_EDGES))
def test_batch_overlap_edge_rows(case):
    """The jnp rank count equals the host pass and the stated verdict on
    touching intervals, empty lists, int32 extremes and padding rows."""
    rows = OVERLAP_EDGES[case]
    Bp = 8
    I = max([len(x) for x, _, _ in rows] + [1])
    J = max([len(y) for _, y, _ in rows] + [1])

    def pack(lists, W):
        s = np.full((Bp, W), I32_MAX, np.int32)
        l = s.copy()
        n = np.zeros(Bp, np.int32)
        for b, ints in enumerate(lists):
            n[b] = len(ints)
            for j, (a, z) in enumerate(ints):
                s[b, j], l[b, j] = a, z
        return s, l, n

    xs, xl, nx = pack([x for x, _, _ in rows], I)
    ys, yl, ny = pack([y for _, y, _ in rows], J)
    want = np.zeros(Bp, bool)
    want[:len(rows)] = [hit for _, _, hit in rows]
    np.testing.assert_array_equal(batch_overlap_np(xs, xl, nx, ys, yl, ny),
                                  want)
    np.testing.assert_array_equal(
        np.asarray(batch_overlap_jnp(xs, xl, nx, ys, yl, ny)), want)


def test_interval_join_kernel_vs_merge_join():
    """Kernel verdict == the paper's sequential merge join on real APRIL data."""
    R = make_dataset("T1", seed=71, count=40)
    S = make_dataset("T2", seed=72, count=40)
    ar, as_ = build_april(R, 7), build_april(S, 7)
    idx_r = np.arange(40); idx_s = np.arange(40)
    xs, xl, nx = pack_lists(ar, idx_r, "A")
    ys, yl, ny = pack_lists(as_, idx_s, "A")
    got = np.asarray(batch_interval_overlap(xs, xl, nx, ys, yl, ny,
                                            interpret=True))
    want = np.asarray([
        interval_join_pair(ar.a_list(i), as_.a_list(j))
        for i, j in zip(idx_r, idx_s)])
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------- ri_and

@pytest.mark.parametrize("B,W,density", [(8, 2, 0.05), (24, 6, 0.08),
                                         (5, 16, 0.02), (12, 4, 0.5)])
def test_ri_and_kernel_sweep(B, W, density):
    rng = np.random.default_rng(B + W)
    xw = np.zeros((B, W), np.uint32); yw = np.zeros((B, W), np.uint32)
    meta = np.zeros((B, 4), np.int32)
    for b in range(B):
        xw[b] = pack_bits_u32((rng.random(32 * W) < density).astype(np.uint8), W)
        yw[b] = pack_bits_u32((rng.random(32 * W) < density).astype(np.uint8), W)
        max_off = max(1, 32 * (W - 2))
        meta[b] = (int(rng.integers(0, max_off)), int(rng.integers(0, max_off)),
                   int(rng.integers(1, 64)), int(rng.integers(0, 2)))
    mask = xor_mask_words(W)
    got = np.asarray(batch_aligned_and(xw, yw, meta, mask, interpret=True))
    want = np.asarray(aligned_and_ref(jnp.asarray(xw), jnp.asarray(yw),
                                      meta, jnp.asarray(mask)))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------- refine

@pytest.mark.parametrize("seed,count", [(81, 16), (82, 24)])
def test_refine_kernel_sweep(seed, count):
    R = make_dataset("T1", seed=seed, count=count)
    S = make_dataset("T2", seed=seed + 1, count=count)
    idx = np.arange(count)
    sa, ea, ma = geometry.polygon_edges(R.verts[idx], R.nverts[idx])
    sb, eb, mb = geometry.polygon_edges(S.verts[idx], S.nverts[idx])
    hit, unc = batch_edges_intersect(sa, ea, ma, sb, eb, mb, interpret=True)
    rh, ru = edges_intersect_ref(jnp.asarray(sa, jnp.float32),
                                 jnp.asarray(ea, jnp.float32), jnp.asarray(ma),
                                 jnp.asarray(sb, jnp.float32),
                                 jnp.asarray(eb, jnp.float32), jnp.asarray(mb))
    np.testing.assert_array_equal(np.asarray(hit), np.asarray(rh))
    np.testing.assert_array_equal(np.asarray(unc), np.asarray(ru))
    # soundness: definite kernel hits must be true intersections (f64 oracle)
    for b in range(count):
        if bool(hit[b]) and not bool(unc[b]):
            assert geometry.polygons_intersect(
                R.verts[b], R.nverts[b], S.verts[b], S.nverts[b])


def test_refine_kernel_overlapping_pairs():
    """Force intersecting pairs (shifted copies) — kernel must find them."""
    R = make_dataset("T1", seed=83, count=12)
    verts2 = R.verts + 1e-4  # tiny shift => guaranteed overlap
    from repro.datagen.synthetic import PolygonDataset
    S = PolygonDataset(name="shift", verts=verts2, nverts=R.nverts)
    idx = np.arange(12)
    sa, ea, ma = geometry.polygon_edges(R.verts[idx], R.nverts[idx])
    sb, eb, mb = geometry.polygon_edges(S.verts[idx], S.nverts[idx])
    hit, unc = batch_edges_intersect(sa, ea, ma, sb, eb, mb, interpret=True)
    assert bool(np.all(np.asarray(hit) | np.asarray(unc)))


# ---------------------------------------------------------------- april_attention

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kind,window,softcap", [
    ("causal", 0, None), ("local", 96, None), ("local", 64, 30.0),
    ("full", 0, None)])
def test_april_attention_sweep(dtype, kind, window, softcap):
    rng = np.random.default_rng(11)
    BH, S, D = 2, 256, 64
    q = jnp.asarray(rng.normal(size=(BH, S, D)), dtype)
    k = jnp.asarray(rng.normal(size=(BH, S, D)), dtype)
    v = jnp.asarray(rng.normal(size=(BH, S, D)), dtype)
    got = april_attention(q, k, v, block_q=64, block_kv=64, mask_kind=kind,
                          window=window, softcap=softcap, interpret=True)
    want = april_attention_ref(q, k, v, mask_kind=kind, window=window,
                               softcap=softcap)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("S,bq,bkv", [(256, 128, 64), (512, 64, 128)])
def test_april_attention_blocks(S, bq, bkv):
    rng = np.random.default_rng(S)
    q = jnp.asarray(rng.normal(size=(1, S, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, S, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, S, 32)), jnp.float32)
    got = april_attention(q, k, v, block_q=bq, block_kv=bkv,
                          mask_kind="causal", interpret=True)
    want = april_attention_ref(q, k, v, mask_kind="causal")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_block_intervals_classification():
    """The interval table must be the exact APRIL A/F classification of the
    (q_block x kv_block) raster of the mask."""
    for kind, window in [("causal", 0), ("local", 96), ("full", 0)]:
        Sq = Skv = 512; bq = bkv = 64
        iv = build_block_intervals(Sq, Skv, bq, bkv, kind, window)
        mask = np.asarray(dense_mask(Sq, Skv, kind, window))
        for qi in range(Sq // bq):
            rows = mask[qi * bq: (qi + 1) * bq]
            for ki in range(Skv // bkv):
                blk = rows[:, ki * bkv: (ki + 1) * bkv]
                a_lo, f_lo, f_hi, a_hi = iv[qi]
                in_a = a_lo <= ki < a_hi
                in_f = f_lo <= ki < f_hi
                if blk.all():
                    assert in_a, (kind, qi, ki)
                    # a Full block must never be treated as maskable-out
                elif blk.any():
                    assert in_a and not in_f, (kind, qi, ki)
                else:
                    assert not in_a or not in_f, (kind, qi, ki)
