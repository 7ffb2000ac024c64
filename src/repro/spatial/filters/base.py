"""The `IntermediateFilter` protocol + registry (DESIGN.md §2).

The paper's pipeline is MBR filter -> *intermediate filter* -> refinement
(Fig. 1). This module makes the intermediate step a first-class, pluggable
abstraction:

* :class:`Approximation` — a built, reusable, sizeable store for one dataset
  (what used to be the ad-hoc ``prebuilt: tuple | None``).
* :class:`IntermediateFilter` — ``build(dataset, *, n_order, extent, ...)``
  produces an Approximation; ``verdicts(approx_r, approx_s, pairs, *,
  predicate, backend)`` classifies a whole candidate batch into the paper's
  trichotomy (TRUE_NEG / TRUE_HIT / INDECISIVE) in one vectorized pass.
  ``verdicts_seq`` is the faithful per-pair reference the batched path must
  be verdict-identical to (asserted by tests/test_filter_protocol.py).
* a name-based registry — :func:`register_filter` / :func:`get_filter` —
  backing ``none / april / april-c / ri / ra / 5cch``.

Predicates: ``intersects`` | ``within`` | ``linestring`` | ``selection``.
``selection`` (polygonal range queries, §4.3.1) is the intersects test with
query polygons as the S side; ``linestring`` (§4.3.3) expects the R side
built with ``kind='line'``.

Backends (``filter_backend`` on :class:`~repro.spatial.plan.JoinPlan`,
DESIGN.md §9): ``numpy`` (host, default), ``jnp`` (bucketed device
batches), ``pallas`` (TPU kernels where available), ``sequential`` (the
faithful per-pair reference loop — every filter dispatches it to
``verdicts_seq``). Filters without a device path for a given predicate
fall back to their vectorized numpy path — backend choice never changes
verdicts.
"""
from __future__ import annotations

import abc
from dataclasses import dataclass, field

import numpy as np

from ...core.join import INDECISIVE
from ...core.join import FILTER_BACKENDS as _FILTER_BACKENDS
from ...core.rasterize import Extent, GLOBAL_EXTENT
from ...kernels import to_device

__all__ = [
    "PREDICATES", "BACKENDS", "FILTER_BACKENDS", "BUILD_BACKENDS",
    "Approximation", "IntermediateFilter",
    "register_filter", "unregister_filter", "get_filter", "available_filters",
]

PREDICATES = ("intersects", "within", "linestring", "selection")
#: verdict-stage execution paths (DESIGN.md §9, the single source of truth
#: in core.join); 'sequential' is the per-pair reference loop, dispatched
#: to ``verdicts_seq`` by every filter
FILTER_BACKENDS = _FILTER_BACKENDS
BACKENDS = FILTER_BACKENDS   # historical alias
#: construction backends (DESIGN.md §6): 'numpy'/'jnp' run the batched
#: dataset-level build; 'sequential' is the per-object reference loop every
#: batched build must be store-identical to.
BUILD_BACKENDS = ("numpy", "jnp", "sequential")


@dataclass
class Approximation:
    """A built intermediate-filter store for one dataset.

    ``store`` is filter-specific (AprilStore, RIStore, RAStore, FiveCCH,
    CompressedAprilStore, or None for the 'none' filter); ``kind`` records
    what was approximated ('polygon' or 'line'); ``meta`` holds reusable
    caches (e.g. RA upscale pyramids) that survive across ``verdicts`` calls
    and predicates.
    """
    filter: str
    store: object
    n_order: int | None = None
    extent: Extent | None = None
    kind: str = "polygon"
    meta: dict = field(default_factory=dict)

    def size_bytes(self) -> int:
        return int(self.store.size_bytes()) if self.store is not None else 0

    def __len__(self) -> int:
        return len(self.store) if self.store is not None else 0


class IntermediateFilter(abc.ABC):
    """One intermediate filter method (paper §2-§5)."""

    name: str = "?"
    #: filters with a mesh-sharded device path (see spatial/distributed.py)
    supports_mesh: bool = False

    # -- preprocessing ------------------------------------------------------
    @abc.abstractmethod
    def build(self, dataset, *, n_order: int = 10,
              extent: Extent = GLOBAL_EXTENT, kind: str = "polygon",
              side: str = "r", **opts) -> Approximation:
        """Build the approximation store for ``dataset``.

        ``kind``: 'polygon' or 'line' (open chains, §4.3.3). ``side`` is a
        hint ('r'/'s') for filters whose encoding differs per join side (RI).
        Every built-in filter accepts ``build_backend`` (one of
        ``BUILD_BACKENDS``): 'numpy' (default) / 'jnp' run the batched
        dataset-level construction, 'sequential' the per-object reference.
        """

    # -- filtering ----------------------------------------------------------
    @abc.abstractmethod
    def verdicts(self, approx_r: Approximation, approx_s: Approximation,
                 pairs: np.ndarray, *, predicate: str = "intersects",
                 backend: str = "numpy", **opts) -> np.ndarray:
        """Batched verdicts [N] int8 for candidate ``pairs`` [N, 2]."""

    def verdicts_seq(self, approx_r: Approximation, approx_s: Approximation,
                     pairs: np.ndarray, *, predicate: str = "intersects",
                     **opts) -> np.ndarray:
        """Faithful per-pair reference loop (the paper's algorithms).

        Subclasses override :meth:`_verdict_one`; this loop is the semantic
        contract the batched path is tested against.
        """
        pairs = np.asarray(pairs, np.int64).reshape(-1, 2)
        self._check(predicate, "numpy")
        return np.asarray(
            [self._verdict_one(approx_r, approx_s, int(i), int(j),
                               predicate=predicate, **opts)
             for i, j in pairs], np.int8)

    def _verdict_one(self, approx_r, approx_s, i: int, j: int, *,
                     predicate: str, **opts) -> int:
        raise NotImplementedError

    def status_lane(self, approx_r: Approximation, approx_s: Approximation,
                    ri: np.ndarray, si: np.ndarray, *,
                    predicate: str = "intersects", backend: str = "numpy",
                    **opts):
        """Device int8 status lane [N] over the fused chain's pair frame
        (DESIGN.md §12).

        ``ri``/``si`` are the host-known candidate frame — grid-hash
        preprocessing artifacts, so consuming them costs no device sync.
        The default computes the batched host :meth:`verdicts` over the
        frame and uploads the result; filters whose stores are
        device-resident (APRIL, none) override with a lane computed on
        device, keeping the chain free of intermediate host pulls. Verdicts
        must be row-identical to :meth:`verdicts` for every backend.
        """
        import jax.numpy as jnp
        ri = np.asarray(ri, np.int64)
        si = np.asarray(si, np.int64)
        if len(ri) == 0:
            return jnp.zeros(0, jnp.int8)
        verd = self.verdicts(approx_r, approx_s, np.stack([ri, si], axis=1),
                             predicate=predicate, backend=backend, **opts)
        return to_device(verd)

    # -- incremental maintenance (DESIGN.md §10) ----------------------------
    def patch_insert(self, approx: Approximation, dataset_one) -> None:
        """Append the approximation of ``dataset_one``'s single object to
        ``approx`` in place (the new object gets id ``len(approx)``).

        The one-object store comes from this filter's own :meth:`build`
        under the ``build_opts`` recorded in ``approx.meta`` at build time;
        construction is per-object independent (the batched build is
        store-identical to the sequential per-object reference), so a
        patched store equals a fresh rebuild over the extended dataset.
        """
        if len(dataset_one) != 1:
            raise ValueError(f"patch_insert expects a 1-object dataset, "
                             f"got {len(dataset_one)}")
        opts = dict(approx.meta.get("build_opts", {}))
        one = self.build(
            dataset_one,
            n_order=approx.n_order if approx.n_order is not None else 10,
            extent=approx.extent if approx.extent is not None
            else GLOBAL_EXTENT, kind=approx.kind, **opts)
        self._store_append(approx, one)

    def patch_delete(self, approx: Approximation, idx: int) -> None:
        """Splice object ``idx`` out of ``approx`` in place; later ids
        shift down by one (the numbering a fresh rebuild would use)."""
        if not 0 <= int(idx) < len(approx):
            raise IndexError(f"patch_delete: id {idx} out of range "
                             f"[0, {len(approx)})")
        self._store_delete(approx, int(idx))

    def _store_append(self, approx: Approximation,
                      one: Approximation) -> None:
        raise NotImplementedError(
            f"filter {self.name!r} has no incremental maintenance path")

    def _store_delete(self, approx: Approximation, idx: int) -> None:
        raise NotImplementedError(
            f"filter {self.name!r} has no incremental maintenance path")

    @staticmethod
    def _drop_derived(approx: Approximation) -> None:
        """Drop per-object derived caches that a row splice invalidates
        (meta caches are index-keyed; ``core.join`` attaches a raw-store
        interval-list cache)."""
        for key in ("interval_lists", "pyramid"):
            approx.meta.pop(key, None)
        store = approx.store
        if store is not None and hasattr(store, "_interval_lists_cache"):
            del store._interval_lists_cache

    # -- optional mesh path (overridden by filters with a device kernel) ----
    def verdicts_mesh(self, approx_r, approx_s, pairs, *, mesh=None,
                      **opts) -> tuple[np.ndarray, dict]:
        raise NotImplementedError(
            f"filter {self.name!r} has no mesh-sharded path")

    # -- helpers ------------------------------------------------------------
    @staticmethod
    def _check_build_backend(build_backend: str) -> None:
        if build_backend not in BUILD_BACKENDS:
            raise ValueError(f"unknown build_backend {build_backend!r}; "
                             f"expected one of {BUILD_BACKENDS}")

    @staticmethod
    def _check(predicate: str, backend: str) -> None:
        if predicate not in PREDICATES:
            raise ValueError(f"unknown predicate {predicate!r}; "
                             f"expected one of {PREDICATES}")
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; "
                             f"expected one of {BACKENDS}")

    @staticmethod
    def _empty(pairs: np.ndarray) -> np.ndarray | None:
        pairs = np.asarray(pairs)
        if pairs.size == 0:
            return np.zeros(0, np.int8)
        return None

    @staticmethod
    def _all_indecisive(pairs: np.ndarray) -> np.ndarray:
        n = len(np.asarray(pairs).reshape(-1, 2))
        return np.full(n, INDECISIVE, np.int8)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, type[IntermediateFilter]] = {}


def register_filter(name: str, cls: type[IntermediateFilter] | None = None):
    """Register a filter class under ``name``. Usable as a decorator::

        @register_filter("april")
        class AprilFilter(IntermediateFilter): ...
    """
    def _do(c):
        c.name = name
        _REGISTRY[name] = c
        return c
    return _do(cls) if cls is not None else _do


def unregister_filter(name: str) -> None:
    _REGISTRY.pop(name, None)


def get_filter(name: str | IntermediateFilter) -> IntermediateFilter:
    """Look up a registered filter by name; instances pass through."""
    if isinstance(name, IntermediateFilter):
        return name
    try:
        return _REGISTRY[name]()
    except KeyError:
        raise ValueError(
            f"unknown intermediate filter {name!r}; "
            f"available: {sorted(_REGISTRY)}") from None


def available_filters() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))
