"""Interval joins: the APRIL intermediate filter (paper §4.2, Algorithm 2).

Three execution styles:

* **Faithful sequential merge joins** (`interval_join_pair`,
  `april_verdict_pair`) — the paper's two-pointer O(n+m) loops with early
  exit. Host/NumPy; used as the reference and for CPU-baseline benchmarks.
* **The bucketed filter-join subsystem** (DESIGN.md §9) —
  :class:`IntervalLists` holds a dataset's interval lists CSR-packed in
  biased int32 with inclusive-last endpoints (see ``april.py``), uploaded
  to the device once and reused across ``JoinPlan`` calls. The staged
  trichotomy drivers (:func:`april_trichotomy_rows`,
  :func:`within_trichotomy_rows`, :func:`linestring_trichotomy_rows`) run
  the cheap AA-join over the whole batch first and forward only the AA
  survivors — compacted, like refinement's CMBR sweep — into the expensive
  full-cell joins. Backends: ``numpy`` evaluates the overlap as one flat
  row-keyed searchsorted pass (no padding, no per-pair loop); ``jnp``
  gathers padded power-of-two width buckets on device and tests overlap
  by dense rank counts (no search loop); ``pallas`` ships
  bucketed batches through ``kernels/interval_join`` (the fused kernel
  computes the whole three-join verdict in one pass).
* **Legacy padded batch joins** (`batch_overlap_np`, `batch_overlap_jnp`,
  `pack_lists`) — pad-to-max layouts kept for the mesh-sharded
  ``PackedPairs`` path (spatial/distributed.py) and the kernel tests.

Verdicts follow the paper's trichotomy: a pair is a sure non-result
(TRUE_NEG, AA-join empty), a sure result (TRUE_HIT, AF- or FA-join finds an
overlap), or INDECISIVE (forwarded to refinement).
"""
from __future__ import annotations

import numpy as np

from ..kernels import to_device
from ..runtime.trace import count, span
from .hilbert import u32_to_biased_i32
from .rasterize import size_buckets

try:
    import jax
    import jax.numpy as jnp
except Exception:  # pragma: no cover
    jax = None
    jnp = None

__all__ = [
    "TRUE_NEG", "TRUE_HIT", "INDECISIVE", "FILTER_BACKENDS",
    "check_filter_backend", "IntervalLists",
    "csr_delete_row", "csr_append_row",
    "interval_join_pair", "april_verdict_pair", "within_verdict_pair",
    "linestring_verdict_pair", "pack_lists", "pack_csr_intervals",
    "overlap_rows_np", "contain_rows_np",
    "april_trichotomy_rows", "within_trichotomy_rows",
    "linestring_trichotomy_rows",
    "batch_overlap_np", "batch_overlap_jnp", "april_filter_batch",
    "within_filter_batch", "linestring_filter_batch",
    "containment_join_pair", "adaptive_order", "fused_status_rows",
]

TRUE_NEG, TRUE_HIT, INDECISIVE = 0, 1, 2
I32_MAX = np.int32(np.iinfo(np.int32).max)

#: execution paths of the intermediate-filter stage (``filter_backend`` on
#: :class:`~repro.spatial.plan.JoinPlan`, DESIGN.md §9): 'numpy' is the flat
#: vectorized host pass, 'jnp' the bucketed device pass, 'pallas' the fused
#: TPU kernel, 'sequential' the faithful per-pair reference loop every
#: batched backend must be verdict-identical to.
FILTER_BACKENDS = ("numpy", "jnp", "pallas", "sequential")


def check_filter_backend(backend: str) -> None:
    if backend not in FILTER_BACKENDS:
        raise ValueError(f"unknown filter backend {backend!r}; "
                         f"expected one of {FILTER_BACKENDS}")


# ---------------------------------------------------------------------------
# CSR row splices (incremental store maintenance, DESIGN.md §10)
# ---------------------------------------------------------------------------

def csr_delete_row(off: np.ndarray, data: np.ndarray, i: int
                   ) -> tuple[np.ndarray, np.ndarray]:
    """Splice row ``i`` out of a CSR (offsets [P+1], flat data) pair.

    The flat segment ``data[off[i]:off[i+1]]`` is removed and later offsets
    shift down — no other row's payload is recomputed. Works for any flat
    axis-0 layout (interval tables [T, 2], cell-id vectors [T], ...).
    """
    off = np.asarray(off, np.int64)
    lo, hi = int(off[i]), int(off[i + 1])
    new_off = np.concatenate([off[:i + 1], off[i + 2:] - (hi - lo)])
    new_data = np.concatenate([data[:lo], data[hi:]], axis=0)
    return new_off, new_data


def csr_append_row(off: np.ndarray, data: np.ndarray, row: np.ndarray
                   ) -> tuple[np.ndarray, np.ndarray]:
    """Append one row (flat payload ``row``) to a CSR pair; existing rows
    are untouched."""
    off = np.asarray(off, np.int64)
    new_off = np.append(off, off[-1] + len(row))
    new_data = np.concatenate([data, row], axis=0)
    return new_off, new_data


# ---------------------------------------------------------------------------
# Faithful sequential joins (paper Algorithm 2, host reference)
# ---------------------------------------------------------------------------

def interval_join_pair(X: np.ndarray, Y: np.ndarray) -> bool:
    """Two-pointer merge join over sorted disjoint half-open intervals.
    Returns True iff any pair overlaps (paper Alg. 2 `IntervalJoin`)."""
    i = j = 0
    nx, ny = len(X), len(Y)
    while i < nx and j < ny:
        xs, xe = X[i]
        ys, ye = Y[j]
        if xs < ye and ys < xe:
            return True
        if xe <= ye:
            i += 1
        else:
            j += 1
    return False


def containment_join_pair(X: np.ndarray, F: np.ndarray) -> bool:
    """True iff EVERY interval of X is contained in some interval of F
    (within-join variant of the AF-join, §4.3.2)."""
    j = 0
    nf = len(F)
    for xs, xe in X:
        while j < nf and F[j][1] < xe:
            j += 1
        if j >= nf or not (F[j][0] <= xs and xe <= F[j][1]):
            return False
    return True


def april_verdict_pair(
    Ar: np.ndarray, Fr: np.ndarray, As: np.ndarray, Fs: np.ndarray,
    order: tuple[str, ...] = ("AA", "AF", "FA"),
) -> int:
    """APRIL intermediate filter for one candidate pair (Algorithm 2).

    ``order`` permutes the three joins (§7.2.2 join-order study). Semantics
    are order-invariant; early exits differ.
    """
    lists = {"AA": (Ar, As), "AF": (Ar, Fs), "FA": (Fr, As)}
    aa_overlap = None
    for step in order:
        X, Y = lists[step]
        hit = interval_join_pair(X, Y)
        if step == "AA":
            aa_overlap = hit
            if not hit:
                return TRUE_NEG
        elif hit:
            return TRUE_HIT
    if aa_overlap is None:   # AA ran last and was True (else returned above)
        raise AssertionError("order must include 'AA'")
    return INDECISIVE


def adaptive_order(mbr_r, mbr_s, nf_r: int, nf_s: int) -> tuple[str, ...]:
    """Per-pair join-order selection (the paper's §9 future-work item).

    Heuristic from object statistics available before any interval work:
    the MBR-overlap fraction of the smaller object predicts hit likelihood.
    Pairs whose common MBR covers most of one object are likely TRUE HITS
    -> run the cheap hit-detecting join (AF/FA, picking the side with the
    larger F-list) first; barely-touching pairs are likely TRUE NEGATIVES
    -> keep AA first (the paper's default).
    """
    ix = max(0.0, min(mbr_r[2], mbr_s[2]) - max(mbr_r[0], mbr_s[0]))
    iy = max(0.0, min(mbr_r[3], mbr_s[3]) - max(mbr_r[1], mbr_s[1]))
    inter = ix * iy
    area_r = max(1e-30, (mbr_r[2] - mbr_r[0]) * (mbr_r[3] - mbr_r[1]))
    area_s = max(1e-30, (mbr_s[2] - mbr_s[0]) * (mbr_s[3] - mbr_s[1]))
    cover = inter / min(area_r, area_s)
    if cover > 0.6 and (nf_r or nf_s):
        return ("AF", "FA", "AA") if nf_s >= nf_r else ("FA", "AF", "AA")
    return ("AA", "AF", "FA")


def within_verdict_pair(Ar, Fr, As, Fs) -> int:
    """Within-join filter (§4.3.2): r within s?  AA disjoint => TRUE_NEG;
    every A(r) interval inside an F(s) interval => TRUE_HIT; else indecisive."""
    if not interval_join_pair(Ar, As):
        return TRUE_NEG
    if len(Ar) and containment_join_pair(Ar, Fs):
        return TRUE_HIT
    return INDECISIVE


def linestring_verdict_pair(Ap, Fp, cell_ids: np.ndarray) -> int:
    """Polygon x linestring filter (§4.3.3). The linestring is a sorted
    Partial cell-id array, treated as unit intervals."""
    cells = np.stack([cell_ids, cell_ids + np.uint64(1)], axis=1) \
        if len(cell_ids) else np.zeros((0, 2), np.uint64)
    if not interval_join_pair(Ap, cells):
        return TRUE_NEG
    if interval_join_pair(Fp, cells):
        return TRUE_HIT
    return INDECISIVE


# ---------------------------------------------------------------------------
# Vectorized batched joins (TPU-adapted; numpy reference + jnp device)
# ---------------------------------------------------------------------------

def pack_csr_intervals(off: np.ndarray, ints: np.ndarray, idx: np.ndarray,
                       pad_to: int | None = None):
    """Pack CSR interval lists ``ints[off[i]:off[i+1]]`` for rows ``idx`` into
    padded biased-int32 arrays.

    Returns (starts [B, I], lasts [B, I], counts [B]) where I is the max (or
    ``pad_to``) interval count; padding slots hold I32_MAX. Endpoints are
    inclusive-last (end-1) in biased-int32 space. Fully vectorized CSR->
    padded gather (no per-pair Python loop — this packing is on the host hot
    path of every device batch).
    """
    idx = np.asarray(idx, np.int64)
    lo = off[idx]
    counts = (off[idx + 1] - lo).astype(np.int32)
    B = len(idx)
    width = int(max(1, counts.max() if B else 1))
    if pad_to is not None:
        width = max(width, pad_to)
    starts = np.full((B, width), I32_MAX, np.int32)
    lasts = np.full((B, width), I32_MAX, np.int32)
    if len(ints) and B:
        col = np.arange(width)[None, :]
        mask = col < counts[:, None]                       # [B, width]
        src = (lo[:, None] + col)[mask]                    # flat gather idx
        starts[mask] = u32_to_biased_i32(ints[src, 0])
        lasts[mask] = u32_to_biased_i32(ints[src, 1] - np.uint64(1))
    return starts, lasts, counts


def pack_lists(store, idx: np.ndarray, kind: str, pad_to: int | None = None):
    """Pack interval lists store[kind][idx]; see :func:`pack_csr_intervals`."""
    off = store.a_off if kind == "A" else store.f_off
    ints = store.a_ints if kind == "A" else store.f_ints
    return pack_csr_intervals(off, ints, idx, pad_to=pad_to)


def batch_overlap_np(xs, xl, nx, ys, yl, ny) -> np.ndarray:
    """NumPy vectorized overlap test per batch row (inclusive-last ints).

    Overlap iff exists (i, j): ys[j] <= xl[i] and xs[i] <= yl[j]. Per x-
    interval, binary-search y-lasts for the first j with yl[j] >= xs[i].
    """
    B, I = xs.shape
    out = np.zeros(B, dtype=bool)
    for b in range(B):  # host reference — device path is the jnp/Pallas one
        nyb = int(ny[b])
        nxb = int(nx[b])
        if nyb == 0 or nxb == 0:
            continue
        j = np.searchsorted(yl[b, :nyb], xs[b, :nxb], side="left")
        ok = j < nyb
        jj = np.minimum(j, nyb - 1)
        out[b] = bool(np.any(ok & (ys[b, jj] <= xl[b, :nxb])))
    return out


def batch_overlap_jnp(xs, xl, nx, ys, yl, ny):
    """jnp device version of :func:`batch_overlap_np`: a rank count, with no
    search loop and no gather.

    Per valid x interval, ``a`` counts the valid y intervals that end
    before x starts and ``b`` those that start by x's end. A row's y starts
    and y lasts are both sorted, so both sets are prefixes of Y with
    ``a <= b``, and y_a overlaps x iff ``b > a``. Each count is a [B, Wx,
    Wy] broadcast compare summed over Wy.
    """
    assert jnp is not None
    valid_y = (jnp.arange(ys.shape[1], dtype=jnp.int32)
               < ny[:, None])[:, None, :]
    a = jnp.sum(valid_y & (yl[:, None, :] < xs[:, :, None]), axis=2,
                dtype=jnp.int32)
    b = jnp.sum(valid_y & (ys[:, None, :] <= xl[:, :, None]), axis=2,
                dtype=jnp.int32)
    valid_x = jnp.arange(xs.shape[1], dtype=jnp.int32) < nx[:, None]
    return jnp.any(valid_x & (b > a), axis=1)


def _containment_batch_np(xs, xl, nx, fs, fl, nf) -> np.ndarray:
    """Every x interval contained in some f interval? (within-join, batched)"""
    B, I = xs.shape
    out = np.zeros(B, dtype=bool)
    for b in range(B):
        nxb, nfb = int(nx[b]), int(nf[b])
        if nxb == 0:
            continue
        if nfb == 0:
            out[b] = False
            continue
        j = np.searchsorted(fl[b, :nfb], xl[b, :nxb], side="left")
        ok = j < nfb
        jj = np.minimum(j, nfb - 1)
        out[b] = bool(np.all(ok & (fs[b, jj] <= xs[b, :nxb])
                             & (xl[b, :nxb] <= fl[b, jj])))
    return out


def batch_containment_jnp(xs, xl, nx, fs, fl, nf):
    """jnp device version of :func:`_containment_batch_np`."""
    assert jnp is not None

    def one(xs_r, xl_r, nx_r, fs_r, fl_r, nf_r):
        I = xs_r.shape[0]
        j = jnp.searchsorted(fl_r, xl_r, side="left")
        ok = j < nf_r
        jj = jnp.minimum(j, jnp.maximum(nf_r - 1, 0))
        fs_at = jnp.take(fs_r, jj)
        fl_at = jnp.take(fl_r, jj)
        valid_x = jnp.arange(I, dtype=jnp.int32) < nx_r
        inside = ok & (fs_at <= xs_r) & (xl_r <= fl_at)
        return jnp.all(jnp.where(valid_x, inside, True)) & (nx_r > 0) & (nf_r > 0)

    return jax.vmap(one)(xs, xl, nx, fs, fl, nf)


def _store_lists(store, kind: str) -> "IntervalLists":
    """Wrap one list kind of an AprilStore into an :class:`IntervalLists`,
    cached on the store so repeated wrapper calls pay the biased-int32
    conversion once, not O(store) per batch (the filter classes cache in
    ``Approximation.meta`` instead)."""
    try:
        cache = store._interval_lists_cache
    except AttributeError:
        cache = store._interval_lists_cache = {}
    if kind not in cache:
        if kind == "A":
            cache[kind] = IntervalLists.from_intervals(store.a_off,
                                                       store.a_ints)
        else:
            cache[kind] = IntervalLists.from_intervals(store.f_off,
                                                       store.f_ints)
    return cache[kind]


def within_filter_batch(store_r, store_s, pairs: np.ndarray,
                        use_jnp: bool = False,
                        backend: str | None = None) -> np.ndarray:
    """Vectorized APRIL within filter (§4.3.2) over candidate pairs [N,2].

    Verdict-identical to :func:`within_verdict_pair` applied per pair:
    AA disjoint -> TRUE_NEG; every A(r) interval inside an F(s) interval ->
    TRUE_HIT; else INDECISIVE. Thin wrapper over
    :func:`within_trichotomy_rows` for raw stores.
    """
    pairs = np.asarray(pairs, np.int64).reshape(-1, 2)
    if len(pairs) == 0:
        return np.zeros(0, np.int8)
    backend = backend or ("jnp" if (use_jnp and jnp is not None) else "numpy")
    return within_trichotomy_rows(
        _store_lists(store_r, "A"), _store_lists(store_s, "A"),
        _store_lists(store_s, "F"), pairs[:, 0], pairs[:, 1],
        backend=backend)


def linestring_filter_batch(store_s, line_off: np.ndarray,
                            line_ids: np.ndarray, pairs: np.ndarray,
                            use_jnp: bool = False,
                            backend: str | None = None) -> np.ndarray:
    """Vectorized polygon x linestring filter (§4.3.3).

    ``pairs`` rows are (line_idx, poly_idx); the linestring side is a CSR
    array of sorted Partial cell ids treated as unit intervals (start = last
    = id in inclusive-last space). Verdict-identical to
    :func:`linestring_verdict_pair`; thin wrapper over
    :func:`linestring_trichotomy_rows` for raw stores.
    """
    pairs = np.asarray(pairs, np.int64).reshape(-1, 2)
    if len(pairs) == 0:
        return np.zeros(0, np.int8)
    backend = backend or ("jnp" if (use_jnp and jnp is not None) else "numpy")
    return linestring_trichotomy_rows(
        IntervalLists.from_unit_cells(line_off, line_ids),
        _store_lists(store_s, "A"), _store_lists(store_s, "F"),
        pairs[:, 0], pairs[:, 1], backend=backend)


def april_filter_batch(
    store_r, store_s, pairs: np.ndarray,
    order: tuple[str, ...] = ("AA", "AF", "FA"),
    use_jnp: bool = False, backend: str | None = None,
) -> np.ndarray:
    """Vectorized APRIL filter over candidate pairs [[r_idx, s_idx], ...].

    Returns verdicts [N] int8; thin wrapper over
    :func:`april_trichotomy_rows` for raw stores (the staged AA ->
    compacted AF/FA evaluation, DESIGN.md §9).
    """
    pairs = np.asarray(pairs, np.int64).reshape(-1, 2)
    if len(pairs) == 0:
        return np.zeros(0, np.int8)
    backend = backend or ("jnp" if (use_jnp and jnp is not None) else "numpy")
    return april_trichotomy_rows(
        _store_lists(store_r, "A"), _store_lists(store_r, "F"),
        _store_lists(store_s, "A"), _store_lists(store_s, "F"),
        pairs[:, 0], pairs[:, 1], backend=backend, order=order)


# ---------------------------------------------------------------------------
# The bucketed filter-join subsystem (DESIGN.md §9)
# ---------------------------------------------------------------------------

_KEY_SHIFT = np.uint64(33)
_KEY_BIAS = np.int64(1) << np.int64(31)

#: per-backend padded working-set bound for one bucket chunk
_BUCKET_CHUNK = 1 << 22
#: pallas buckets cap list width so the [BB, I, J] predicate tile fits VMEM
_PALLAS_MAX_WIDTH = 256


class IntervalLists:
    """One dataset side's interval lists, CSR-packed for the filter join.

    Endpoints are biased int32 with inclusive lasts (``end - 1``), the
    device-native layout of every batched backend. Built once per
    :class:`~repro.spatial.filters.base.Approximation` (cached in its
    ``meta``) and — for the jnp/pallas backends — uploaded to the device
    once and reused across ``JoinPlan`` calls; per-batch work is a gather,
    never a host re-pack.
    """

    __slots__ = ("off", "starts", "lasts", "_device")

    def __init__(self, off: np.ndarray, starts: np.ndarray,
                 lasts: np.ndarray):
        self.off = np.ascontiguousarray(off, np.int64)
        self.starts = np.ascontiguousarray(starts, np.int32)
        self.lasts = np.ascontiguousarray(lasts, np.int32)
        self._device = None

    @classmethod
    def from_intervals(cls, off: np.ndarray, ints: np.ndarray):
        """From a CSR uint64 half-open interval table (AprilStore layout)."""
        if len(ints):
            starts = u32_to_biased_i32(ints[:, 0])
            lasts = u32_to_biased_i32(ints[:, 1] - np.uint64(1))
        else:
            starts = np.zeros(0, np.int32)
            lasts = np.zeros(0, np.int32)
        return cls(off, starts, lasts)

    @classmethod
    def from_unit_cells(cls, off: np.ndarray, ids: np.ndarray):
        """From sorted cell ids treated as unit intervals (start == last)."""
        b = u32_to_biased_i32(ids) if len(ids) else np.zeros(0, np.int32)
        return cls(off, b, b)

    def __len__(self) -> int:
        return len(self.off) - 1

    def counts(self, idx: np.ndarray) -> np.ndarray:
        idx = np.asarray(idx, np.int64)
        return (self.off[idx + 1] - self.off[idx]).astype(np.int64)

    def pack(self, idx: np.ndarray, width: int):
        """Padded host gather: (starts [B, width], lasts, counts [B])."""
        idx = np.asarray(idx, np.int64)
        lo = self.off[idx]
        cnt = (self.off[idx + 1] - lo).astype(np.int32)
        B = len(idx)
        xs = np.full((B, width), I32_MAX, np.int32)
        xl = np.full((B, width), I32_MAX, np.int32)
        if len(self.starts) and B:
            col = np.arange(width)[None, :]
            mask = col < cnt[:, None]
            src = (lo[:, None] + col)[mask]
            xs[mask] = self.starts[src]
            xl[mask] = self.lasts[src]
        return xs, xl, cnt

    def device(self):
        """Lazily uploaded device copies of the flat endpoint arrays."""
        if self._device is None:
            assert jnp is not None, "jax unavailable"
            # a sentinel slot lets empty stores still index safely on device
            s = self.starts if len(self.starts) else np.full(1, I32_MAX,
                                                             np.int32)
            l = self.lasts if len(self.lasts) else np.full(1, I32_MAX,
                                                           np.int32)
            self._device = (to_device(s), to_device(l))
        return self._device

    # -- incremental maintenance (row splices, DESIGN.md §10) ---------------

    def delete_row(self, i: int) -> None:
        """Splice row ``i`` out in place; only this row's endpoints move.
        Drops the device copy — the next device batch re-uploads the
        patched flat arrays."""
        old_off = self.off
        _, self.lasts = csr_delete_row(old_off, self.lasts, i)
        self.off, self.starts = csr_delete_row(old_off, self.starts, i)
        self._device = None

    def append_row(self, starts: np.ndarray, lasts: np.ndarray) -> None:
        """Append one row's biased-int32 endpoints in place."""
        old_off = self.off
        _, self.lasts = csr_append_row(old_off, self.lasts,
                                       np.asarray(lasts, np.int32))
        self.off, self.starts = csr_append_row(old_off, self.starts,
                                               np.asarray(starts, np.int32))
        self._device = None


def _flat_rows(L: IntervalLists, idx: np.ndarray):
    """Expand rows ``idx`` of ``L`` into flat (row-of-entry [T],
    global-interval [T], counts [B]) arrays."""
    idx = np.asarray(idx, np.int64)
    lo = L.off[idx]
    cnt = (L.off[idx + 1] - lo).astype(np.int64)
    b_of = np.repeat(np.arange(len(idx)), cnt)
    pos = np.arange(len(b_of)) - np.repeat(np.cumsum(cnt) - cnt, cnt)
    return b_of, lo[b_of] + pos, cnt


def _rowkey(b_of: np.ndarray, vals_i32: np.ndarray) -> np.ndarray:
    """Row-keyed sort keys: row index in the high bits, the (order-
    preserving) unbiased endpoint in the low 32."""
    return ((b_of.astype(np.uint64) << _KEY_SHIFT)
            + (vals_i32.astype(np.int64) + _KEY_BIAS).astype(np.uint64))


def overlap_rows_np(X: IntervalLists, xi: np.ndarray,
                    Y: IntervalLists, yi: np.ndarray) -> np.ndarray:
    """[N] bool: does X[xi[n]] overlap Y[yi[n]]? One flat vectorized pass.

    Per x interval, binary-search the row-keyed flat y-lasts for the first
    y with ``yl >= xs`` (row keys keep each pair's segment separate), then
    test ``ys <= xl`` — no padding, no per-pair Python loop.
    """
    xi = np.asarray(xi, np.int64)
    N = len(xi)
    out = np.zeros(N, bool)
    if N == 0:
        return out
    bx, gx, _ = _flat_rows(X, xi)
    by, gy, cy = _flat_rows(Y, yi)
    if len(bx) == 0 or len(by) == 0:
        return out
    ykeys = _rowkey(by, Y.lasts[gy])
    yend = np.cumsum(cy)
    j = np.searchsorted(ykeys, _rowkey(bx, X.starts[gx]), side="left")
    ok = j < yend[bx]
    jj = np.minimum(j, len(gy) - 1)
    hit = ok & (Y.starts[gy[jj]] <= X.lasts[gx])
    out[bx[hit]] = True
    return out


def contain_rows_np(X: IntervalLists, xi: np.ndarray,
                    F: IntervalLists, fi: np.ndarray) -> np.ndarray:
    """[N] bool: is every interval of X[xi[n]] contained in some interval of
    F[fi[n]]? (within-join AF test, §4.3.2). False for empty X or F lists
    — the trichotomy drivers only consult it on AA survivors."""
    xi = np.asarray(xi, np.int64)
    N = len(xi)
    out = (X.counts(xi) > 0) & (F.counts(fi) > 0)
    if N == 0:
        return out
    bx, gx, _ = _flat_rows(X, xi)
    bf, gf, cf = _flat_rows(F, fi)
    if len(bx) == 0 or len(bf) == 0:
        return out      # some side is empty on every row
    fkeys = _rowkey(bf, F.lasts[gf])
    fend = np.cumsum(cf)
    j = np.searchsorted(fkeys, _rowkey(bx, X.lasts[gx]), side="left")
    ok = j < fend[bx]
    jj = np.minimum(j, len(gf) - 1)
    inside = ok & (F.starts[gf[jj]] <= X.starts[gx]) \
        & (X.lasts[gx] <= F.lasts[gf[jj]])
    out[bx[~inside]] = False
    return out


# -- jnp bucketed device paths ----------------------------------------------

def _pow2(n: int) -> int:
    return 1 << max(0, int(np.ceil(np.log2(max(1, int(n))))))


def _device_gather(flat_s, flat_l, lo, cnt, W: int):
    """Padded [B, W] device gather out of the resident flat arrays."""
    col = jnp.arange(W, dtype=jnp.int32)[None, :]
    idx = jnp.clip(lo[:, None] + col, 0, flat_s.shape[0] - 1)
    mask = col < cnt[:, None]
    return (jnp.where(mask, flat_s[idx], I32_MAX),
            jnp.where(mask, flat_l[idx], I32_MAX))


def _overlap_bucket_jnp(xs_f, xl_f, xlo, xcnt, ys_f, yl_f, ylo, ycnt,
                        Wx: int, Wy: int):
    xs, xl = _device_gather(xs_f, xl_f, xlo, xcnt, Wx)
    ys, yl = _device_gather(ys_f, yl_f, ylo, ycnt, Wy)
    return batch_overlap_jnp(xs, xl, xcnt, ys, yl, ycnt)


def _contain_bucket_jnp(xs_f, xl_f, xlo, xcnt, fs_f, fl_f, flo, fcnt,
                        Wx: int, Wf: int):
    xs, xl = _device_gather(xs_f, xl_f, xlo, xcnt, Wx)
    fs, fl = _device_gather(fs_f, fl_f, flo, fcnt, Wf)
    return batch_containment_jnp(xs, xl, xcnt, fs, fl, fcnt)


_JNP_BUCKET_FNS: dict = {}


def _jitted_bucket_fn(kind: str):
    if jax is None:  # pragma: no cover
        raise RuntimeError("jax unavailable for the jnp filter backend")
    if kind not in _JNP_BUCKET_FNS:
        fn = _overlap_bucket_jnp if kind == "overlap" else _contain_bucket_jnp
        _JNP_BUCKET_FNS[kind] = jax.jit(fn, static_argnames=("Wx", "Wy")
                                        if kind == "overlap"
                                        else ("Wx", "Wf"))
    return _JNP_BUCKET_FNS[kind]


def _bucketed_rows_jnp(kind: str, X: IntervalLists, xi, Y: IntervalLists,
                       yi) -> np.ndarray:
    """Bucketed device evaluation of overlap/containment rows.

    Rows group by the power-of-two class of their wider list (padding waste
    <= 2x); each bucket pads its batch to a power of two so the jitted
    gather-and-test step compiles O(log^2) times, not per shape. The
    flat endpoint arrays live on device (:meth:`IntervalLists.device`);
    only the [B] row offsets/counts travel per call.
    """
    xi = np.asarray(xi, np.int64)
    yi = np.asarray(yi, np.int64)
    N = len(xi)
    out = np.zeros(N, bool)
    if N == 0:
        return out
    cx = X.counts(xi)
    cy = Y.counts(yi)
    # rows with an empty list on either side are False for both overlap and
    # (survivor-only) containment; size_buckets skips the zeroed rows
    widths = np.where((cx > 0) & (cy > 0), np.maximum(np.maximum(cx, cy), 1),
                      0)
    fn = _jitted_bucket_fn(kind)
    xs_f, xl_f = X.device()
    ys_f, yl_f = Y.device()
    for sel in size_buckets(widths, _BUCKET_CHUNK):
        Wx = _pow2(cx[sel].max())
        Wy = _pow2(cy[sel].max())
        Bp = _pow2(len(sel))
        xlo = np.zeros(Bp, np.int64)
        xct = np.zeros(Bp, np.int32)
        ylo = np.zeros(Bp, np.int64)
        yct = np.zeros(Bp, np.int32)
        xlo[:len(sel)] = X.off[xi[sel]]
        xct[:len(sel)] = cx[sel]
        ylo[:len(sel)] = Y.off[yi[sel]]
        yct[:len(sel)] = cy[sel]
        kw = {"Wx": Wx, "Wy": Wy} if kind == "overlap" else \
            {"Wx": Wx, "Wf": Wy}
        got = np.asarray(fn(xs_f, xl_f, jnp.asarray(xlo), jnp.asarray(xct),
                            ys_f, yl_f, jnp.asarray(ylo), jnp.asarray(yct),
                            **kw))
        out[sel] = got[:len(sel)]
    return out


def overlap_rows_jnp(X, xi, Y, yi) -> np.ndarray:
    return _bucketed_rows_jnp("overlap", X, xi, Y, yi)


def contain_rows_jnp(X, xi, F, fi) -> np.ndarray:
    return _bucketed_rows_jnp("contain", X, xi, F, fi)


def _overlap_rows_pallas(X, xi, Y, yi) -> np.ndarray:
    """Bucketed overlap through the Pallas ``kernels/interval_join`` kernel
    (interpret mode off-TPU). Used by predicates without a fused kernel.

    Rows whose lists exceed ``_PALLAS_MAX_WIDTH`` would blow the kernel's
    padded [BB, I, J] VMEM tile; they take the flat host pass instead
    (verdict-identical by construction)."""
    from ..kernels import interpret_mode, note_routed, pad_rows_pow2
    from ..kernels.interval_join.ops import batch_interval_overlap
    xi = np.asarray(xi, np.int64)
    yi = np.asarray(yi, np.int64)
    N = len(xi)
    out = np.zeros(N, bool)
    cx = X.counts(xi)
    cy = Y.counts(yi)
    widths = np.maximum(np.maximum(cx, cy), 1)
    live = (cx > 0) & (cy > 0)
    wide = live & (widths > _PALLAS_MAX_WIDTH)
    note_routed("filter_wide_rows_host", np.count_nonzero(wide))
    if wide.any():
        w = np.nonzero(wide)[0]
        out[w] = overlap_rows_np(X, xi[w], Y, yi[w])
    for sel in size_buckets(np.where(live & ~wide, widths, 0), _BUCKET_CHUNK):
        packed, n = pad_rows_pow2([*X.pack(xi[sel], _pow2(cx[sel].max())),
                                   *Y.pack(yi[sel], _pow2(cy[sel].max()))])
        out[sel] = np.asarray(batch_interval_overlap(
            *packed, interpret=interpret_mode()))[:n]
    return out


def _overlap_fn(backend: str):
    if backend == "numpy":
        return overlap_rows_np
    if backend == "jnp":
        return overlap_rows_jnp
    if backend == "pallas":
        return _overlap_rows_pallas
    raise ValueError(f"no batched overlap path for backend {backend!r}")


# -- staged trichotomy drivers ----------------------------------------------

def april_trichotomy_rows(
    Xa: IntervalLists, Xf: IntervalLists, Ya: IntervalLists,
    Yf: IntervalLists, ri: np.ndarray, si: np.ndarray, *,
    backend: str = "numpy", order: tuple[str, ...] = ("AA", "AF", "FA"),
) -> np.ndarray:
    """Staged APRIL trichotomy (Algorithm 2) over rows (ri[n], si[n]).

    The AA-join runs over the whole batch; AF/FA evaluate only the
    compacted AA survivors (the batch analogue of the sequential early
    exit — ``order`` picks which hit-join runs first, semantics are
    order-invariant). The pallas backend instead ships each bucket through
    the fused three-join kernel (one pass, one verdict).
    """
    if "AA" not in order:
        raise ValueError("order must include 'AA'")
    ri = np.asarray(ri, np.int64)
    si = np.asarray(si, np.int64)
    N = len(ri)
    if N == 0:
        return np.zeros(0, np.int8)
    # the fused kernel evaluates all three joins, which is verdict-identical
    # for any permutation; degenerate orders (hit joins omitted) stage
    if backend == "pallas" and set(order) == {"AA", "AF", "FA"}:
        return _april_trichotomy_pallas(Xa, Xf, Ya, Yf, ri, si)
    overlap = _overlap_fn(backend)
    aa = overlap(Xa, ri, Ya, si)
    verdicts = np.where(aa, INDECISIVE, TRUE_NEG).astype(np.int8)
    sel = np.nonzero(aa)[0]
    # hit joins run in `order`; a degenerate order without them leaves AA
    # survivors INDECISIVE, exactly like the sequential reference
    for step in [s for s in order if s != "AA"]:
        if len(sel) == 0:
            break
        if step == "AF":
            hit = overlap(Xa, ri[sel], Yf, si[sel])
        else:
            hit = overlap(Xf, ri[sel], Ya, si[sel])
        verdicts[sel[hit]] = TRUE_HIT
        sel = sel[~hit]
    return verdicts


def _april_trichotomy_pallas(Xa, Xf, Ya, Yf, ri, si) -> np.ndarray:
    """Bucketed batches through the fused three-join Pallas kernel.

    Rows whose widest list exceeds ``_PALLAS_MAX_WIDTH`` take the flat host
    staged pass instead of blowing the kernel's VMEM tile."""
    from ..kernels import interpret_mode, note_routed, pad_rows_pow2
    from ..kernels.interval_join.ops import batch_april_trichotomy
    N = len(ri)
    verdicts = np.full(N, TRUE_NEG, np.int8)
    counts = [L.counts(idx) for L, idx in
              ((Xa, ri), (Xf, ri), (Ya, si), (Yf, si))]
    widths = np.maximum(np.maximum.reduce(counts), 1)
    # rows with an empty A list on either side are decided without a kernel
    live = (counts[0] > 0) & (counts[2] > 0)
    wide = live & (widths > _PALLAS_MAX_WIDTH)
    note_routed("filter_wide_rows_host", np.count_nonzero(wide))
    if wide.any():
        w = np.nonzero(wide)[0]
        verdicts[w] = april_trichotomy_rows(Xa, Xf, Ya, Yf, ri[w], si[w],
                                            backend="numpy")
    for sel in size_buckets(np.where(live & ~wide, widths, 0), _BUCKET_CHUNK):
        ra = Xa.pack(ri[sel], _pow2(counts[0][sel].max()))
        rf = Xf.pack(ri[sel], _pow2(max(1, counts[1][sel].max())))
        sa = Ya.pack(si[sel], _pow2(counts[2][sel].max()))
        sf = Yf.pack(si[sel], _pow2(max(1, counts[3][sel].max())))
        packed, n = pad_rows_pow2([*ra, *rf, *sa, *sf])
        verdicts[sel] = batch_april_trichotomy(
            *packed, interpret=interpret_mode())[:n]
    return verdicts


# -- fused device status lanes (DESIGN.md §12) -------------------------------

_FUSED_STATUS_FNS: dict = {}


def _fused_tri_bucket_jnp(xa_s, xa_l, xalo, xacnt, xf_s, xf_l, xflo, xfcnt,
                          ya_s, ya_l, yalo, yacnt, yf_s, yf_l, yflo, yfcnt,
                          Wxa: int, Wxf: int, Wya: int, Wyf: int):
    """One bucket of the fused APRIL trichotomy: AA + AF + FA evaluated
    branch-free over every row (no host compaction of AA survivors) and the
    verdict select, all in one traced program."""
    xas, xal = _device_gather(xa_s, xa_l, xalo, xacnt, Wxa)
    xfs, xfl = _device_gather(xf_s, xf_l, xflo, xfcnt, Wxf)
    yas, yal = _device_gather(ya_s, ya_l, yalo, yacnt, Wya)
    yfs, yfl = _device_gather(yf_s, yf_l, yflo, yfcnt, Wyf)
    aa = batch_overlap_jnp(xas, xal, xacnt, yas, yal, yacnt)
    af = batch_overlap_jnp(xas, xal, xacnt, yfs, yfl, yfcnt)
    fa = batch_overlap_jnp(xfs, xfl, xfcnt, yas, yal, yacnt)
    return jnp.where(~aa, TRUE_NEG,
                     jnp.where(af | fa, TRUE_HIT, INDECISIVE)).astype(jnp.int8)


def _fused_within_bucket_jnp(xa_s, xa_l, xalo, xacnt, ya_s, ya_l, yalo, yacnt,
                             yf_s, yf_l, yflo, yfcnt,
                             Wxa: int, Wya: int, Wyf: int):
    """One bucket of the fused within trichotomy: AA overlap + A(r)-in-F(s)
    containment, verdict select in one traced program."""
    xas, xal = _device_gather(xa_s, xa_l, xalo, xacnt, Wxa)
    yas, yal = _device_gather(ya_s, ya_l, yalo, yacnt, Wya)
    yfs, yfl = _device_gather(yf_s, yf_l, yflo, yfcnt, Wyf)
    aa = batch_overlap_jnp(xas, xal, xacnt, yas, yal, yacnt)
    cont = batch_containment_jnp(xas, xal, xacnt, yfs, yfl, yfcnt)
    return jnp.where(~aa, TRUE_NEG,
                     jnp.where(cont, TRUE_HIT, INDECISIVE)).astype(jnp.int8)


def _fused_line_bucket_jnp(c_s, c_l, clo, ccnt, ya_s, ya_l, yalo, yacnt,
                           yf_s, yf_l, yflo, yfcnt,
                           Wc: int, Wya: int, Wyf: int):
    """One bucket of the fused linestring trichotomy: chain cells against
    A(s) and F(s), verdict select in one traced program."""
    cs, cl = _device_gather(c_s, c_l, clo, ccnt, Wc)
    yas, yal = _device_gather(ya_s, ya_l, yalo, yacnt, Wya)
    yfs, yfl = _device_gather(yf_s, yf_l, yflo, yfcnt, Wyf)
    aa = batch_overlap_jnp(cs, cl, ccnt, yas, yal, yacnt)
    fhit = batch_overlap_jnp(cs, cl, ccnt, yfs, yfl, yfcnt)
    return jnp.where(~aa, TRUE_NEG,
                     jnp.where(fhit, TRUE_HIT, INDECISIVE)).astype(jnp.int8)


#: the (x, y) list widths of each overlap test a fused bucket program runs
#: (``within``'s containment test is not one)
_FUSED_OVERLAP_TESTS = {
    "intersects": (("Wxa", "Wya"), ("Wxa", "Wyf"), ("Wxf", "Wya")),
    "within": (("Wxa", "Wya"),),
    "linestring": (("Wc", "Wya"), ("Wc", "Wyf")),
}


def _fused_status_fn(kind: str):
    if jax is None:  # pragma: no cover
        raise RuntimeError("jax unavailable for the fused filter stage")
    if kind not in _FUSED_STATUS_FNS:
        fn, widths = {
            "intersects": (_fused_tri_bucket_jnp,
                           ("Wxa", "Wxf", "Wya", "Wyf")),
            "within": (_fused_within_bucket_jnp, ("Wxa", "Wya", "Wyf")),
            "linestring": (_fused_line_bucket_jnp, ("Wc", "Wya", "Wyf")),
        }[kind]
        _FUSED_STATUS_FNS[kind] = jax.jit(fn, static_argnames=widths)
    return _FUSED_STATUS_FNS[kind]


def _bucket_rows(L: IntervalLists, idx, cnt, sel, Bp: int):
    """One list side's padded [Bp] row offsets/counts for a bucket, on the
    host (padding rows count 0); the program reads them against the
    resident flat endpoint arrays ``L.device()``."""
    lo = np.zeros(Bp, np.int64)
    ct = np.zeros(Bp, np.int32)
    lo[:len(sel)] = L.off[idx[sel]]
    ct[:len(sel)] = cnt[sel]
    return lo, ct


def fused_status_rows(predicate: str, Xa: IntervalLists,
                      Xf: "IntervalLists | None", Ya: IntervalLists,
                      Yf: IntervalLists, ri: np.ndarray, si: np.ndarray):
    """Device int8 status lane over ALL rows — the fused chain's filter
    stage (DESIGN.md §12).

    Unlike the staged drivers above, nothing returns to host: every live
    row's full trichotomy evaluates branch-free per power-of-two width
    bucket and scatters into the [N] device lane (rows with an empty A list
    on either side stay TRUE_NEG, like the staged paths). ``predicate`` is
    'intersects' (Xf required), 'within' (Xf unused) or 'linestring' (Xa is
    the chain's unit-cell lists). Verdict-identical to the staged drivers.

    Each bucket program gathers ``Bp`` padded rows of every list at its
    power-of-two width ``W``: the trace block counts the programs
    (``filter_buckets``), the live rows (``filter_rows``), the padded rows
    (``filter_padded_rows``), the gathered bytes (``filter_gather_bytes``,
    ``Bp * sum(W) * 8``: a start and a last, int32 each, per slot) and the
    rank counts' compares (``filter_compare_ops``, ``Bp * 2 * Wx * Wy``
    summed over the bucket's overlap tests: two counts per x interval, each
    over every y slot). Each
    bucket's span ``repro.filter.bucket`` holds ``repro.filter.args``, its
    host-only argument build, and ``repro.filter.dispatch``, the uploads,
    the program call and the lane scatter, which can block while the
    device is busy.
    """
    ri = np.asarray(ri, np.int64)
    si = np.asarray(si, np.int64)
    N = len(ri)
    lane = jnp.zeros(N, jnp.int8)               # TRUE_NEG
    if N == 0:
        return lane
    with span("repro.filter.plan"):
        ca_r = Xa.counts(ri)
        ca_s = Ya.counts(si)
        cf_s = Yf.counts(si)
        live = (ca_r > 0) & (ca_s > 0)
        if predicate == "intersects":
            cf_r = Xf.counts(ri)
            widths = np.maximum.reduce([ca_r, cf_r, ca_s, cf_s])
        else:
            widths = np.maximum.reduce([ca_r, ca_s, cf_s])
        # lazy: each width class's rows are found only when the loop
        # reaches it, so that host work overlaps the buckets already on
        # the device
        buckets = size_buckets(np.where(live, np.maximum(widths, 1), 0),
                               _BUCKET_CHUNK)
    fn = _fused_status_fn(predicate)
    while True:
        with span("repro.filter.bucket"):
            with span("repro.filter.args"):
                sel = next(buckets, None)
                if sel is None:
                    break
                Bp = _pow2(len(sel))
                sides = [(Xa, ri, ca_r)]
                kw = {}
                if predicate == "intersects":
                    sides.append((Xf, ri, cf_r))
                    kw["Wxa"] = _pow2(ca_r[sel].max())
                    kw["Wxf"] = _pow2(max(1, cf_r[sel].max()))
                else:
                    key = "Wc" if predicate == "linestring" else "Wxa"
                    kw[key] = _pow2(ca_r[sel].max())
                sides += [(Ya, si, ca_s), (Yf, si, cf_s)]
                kw["Wya"] = _pow2(ca_s[sel].max())
                kw["Wyf"] = _pow2(max(1, cf_s[sel].max()))
                rows = [(L, *_bucket_rows(L, idx, cnt, sel, Bp))
                        for L, idx, cnt in sides]
            with span("repro.filter.dispatch"):
                args = ()
                for L, lo, ct in rows:
                    args += (*L.device(), to_device(lo), to_device(ct))
                st = fn(*args, **kw)
                lane = lane.at[to_device(sel)].set(st[:len(sel)])
            count("filter_buckets")
            count("filter_rows", len(sel))
            count("filter_padded_rows", Bp)
            count("filter_gather_bytes", Bp * sum(kw.values()) * 8)
            count("filter_compare_ops", Bp * 2 * sum(
                kw[x] * kw[y] for x, y in _FUSED_OVERLAP_TESTS[predicate]))
    return lane


def within_trichotomy_rows(
    Xa: IntervalLists, Ya: IntervalLists, Yf: IntervalLists,
    ri: np.ndarray, si: np.ndarray, *, backend: str = "numpy",
) -> np.ndarray:
    """Staged within trichotomy (§4.3.2): AA over the batch, containment of
    A(r) in F(s) only on the compacted AA survivors."""
    ri = np.asarray(ri, np.int64)
    si = np.asarray(si, np.int64)
    N = len(ri)
    if N == 0:
        return np.zeros(0, np.int8)
    # containment has no pallas kernel; the pallas backend runs AA through
    # the kernel and falls back to the device containment pass
    overlap = _overlap_fn(backend)
    contain = contain_rows_jnp if backend in ("jnp", "pallas") \
        else contain_rows_np
    aa = overlap(Xa, ri, Ya, si)
    verdicts = np.where(aa, INDECISIVE, TRUE_NEG).astype(np.int8)
    sel = np.nonzero(aa)[0]
    if len(sel):
        cont = contain(Xa, ri[sel], Yf, si[sel])
        verdicts[sel[cont]] = TRUE_HIT
    return verdicts


def linestring_trichotomy_rows(
    C: IntervalLists, Ya: IntervalLists, Yf: IntervalLists,
    li: np.ndarray, si: np.ndarray, *, backend: str = "numpy",
) -> np.ndarray:
    """Staged polygon x linestring trichotomy (§4.3.3): the chain's unit
    intervals against A(s) over the batch, against F(s) on survivors."""
    li = np.asarray(li, np.int64)
    si = np.asarray(si, np.int64)
    N = len(li)
    if N == 0:
        return np.zeros(0, np.int8)
    overlap = _overlap_fn(backend)
    aa = overlap(C, li, Ya, si)
    verdicts = np.where(aa, INDECISIVE, TRUE_NEG).astype(np.int8)
    sel = np.nonzero(aa)[0]
    if len(sel):
        fhit = overlap(C, li[sel], Yf, si[sel])
        verdicts[sel[fhit]] = TRUE_HIT
    return verdicts
