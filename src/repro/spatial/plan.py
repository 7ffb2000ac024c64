"""`JoinPlan`: the session API of the spatial-join pipeline (DESIGN.md §2).

Separates *preprocessing* from *execution*:

    plan = JoinPlan(R, S, filter="ri", filter_backend="numpy", n_order=9)
    plan.build()                               # approximations, reusable
    hits, stats = plan.execute("intersects")   # batched filter + refinement
    within, st2 = plan.execute("within")       # same approximations, free

Every execution runs the paper's stages dataset-batched end to end — MBR
candidate generation (one partitioned grid-hash join, §8) -> intermediate
filter (one batched ``verdicts`` call, §3) -> refinement of the indecisive
remainder (one bucketed exact-geometry pass, §7) — and returns
:class:`JoinStats` with per-stage host times, the shape of the paper's
Tables 5/13/16/17 and Fig. 13, and the run's trace block (DESIGN.md §12).
Each stage's execution path is a backend knob (``mbr_backend`` /
``filter_backend`` / ``refine_backend``, plus
``build_opts["build_backend"]`` for construction, §6); backends change
execution, never results.
"""
from __future__ import annotations

import time
import warnings
from dataclasses import dataclass, field, fields

import numpy as np

from ..core.join import (INDECISIVE, TRUE_HIT, TRUE_NEG,
                         check_filter_backend)
from ..core.rasterize import Extent, GLOBAL_EXTENT
from ..runtime.trace import span, trace_block
from . import refine
from .filters import Approximation, IntermediateFilter, get_filter
from .fused import PIPELINE_MODES, check_pipeline_mode, execute_fused
from .mbr_join import _check_backend as _check_mbr_backend
from .mbr_join import mbr_join
from .planner import PLAN_MODES, PlanChoice, check_plan_mode, choose_plan

__all__ = ["JoinStats", "JoinPlan", "PIPELINE_MODES", "PLAN_MODES"]


@dataclass
class JoinStats:
    method: str
    predicate: str = "intersects"
    backend: str = "numpy"             # historical alias of filter_backend
    filter_backend: str = "numpy"
    refine_backend: str = "numpy"
    mbr_backend: str = "numpy"
    n_candidates: int = 0
    n_true_hits: int = 0
    n_true_negs: int = 0
    n_indecisive: int = 0
    n_results: int = 0
    pipeline_mode: str = "staged"
    #: how the executed configuration was chosen (DESIGN.md §13):
    #: ``static`` = constructor knobs verbatim, ``adaptive`` = planner pick
    #: (the chosen :class:`~repro.spatial.planner.PlanChoice` rides in
    #: ``extra["plan"]``)
    plan_mode: str = "static"
    #: §14 tiled scale-out only: number of memory-budgeted tiles the run
    #: was packed into (0 = in-memory join, no tiling)
    tiles: int = 0
    #: host seconds of each stage's span (``repro.mbr`` / ``repro.filter``
    #: / ``repro.refine``); in fused mode they cover dispatch only, and the
    #: device work lands in ``t_sync``
    t_mbr: float = 0.0
    t_filter: float = 0.0
    t_refine: float = 0.0
    #: fused mode only: host seconds of the end-of-chain gather + f64
    #: escalation, which wait for the chain's device work (staged stage
    #: times include their own syncs, so this stays 0.0 there)
    t_sync: float = 0.0
    t_build: float = 0.0
    #: §14 tiled scale-out only: wall time of the streaming partitioner
    #: (spill + statistics + skew split + tile packing)
    t_partition: float = 0.0
    approx_bytes: int = 0
    extra: dict = field(default_factory=dict)

    @property
    def t_total(self) -> float:
        return self.t_mbr + self.t_filter + self.t_refine + self.t_sync

    def stage_times(self) -> dict:
        """Per-stage host-time breakdown (the serving latency report):
        host seconds, not device time — in fused mode the stage times are
        dispatch only and the device work lands in ``t_sync``. JSON-safe,
        round-trips through to_dict/from_dict."""
        return {"t_mbr": float(self.t_mbr), "t_filter": float(self.t_filter),
                "t_refine": float(self.t_refine),
                "t_sync": float(self.t_sync),
                "t_partition": float(self.t_partition),
                "t_total": float(self.t_total)}

    def rates(self) -> tuple[float, float, float]:
        n = max(1, self.n_candidates)
        return (self.n_true_hits / n, self.n_true_negs / n,
                self.n_indecisive / n)

    def row(self) -> str:
        h, g, i = self.rates()
        sync = (f"sync={self.t_sync:.3f}s "
                if self.pipeline_mode == "fused" else "")
        if self.tiles:
            sync += f"tiles={self.tiles} part={self.t_partition:.3f}s "
        return (f"{self.method:8s} hits={h:6.2%} negs={g:6.2%} indec={i:6.2%} "
                f"mbr={self.t_mbr:.3f}s[{self.mbr_backend}] "
                f"filter={self.t_filter:.3f}s[{self.filter_backend}] "
                f"refine={self.t_refine:.3f}s[{self.refine_backend}] "
                f"{sync}total={self.t_total:.3f}s results={self.n_results}")

    def to_dict(self) -> dict:
        """JSON-safe dict of every field (the service response envelope);
        ``t_build`` rides along — warm-vs-cold build time is the headline
        serving metric. Round-trips through :meth:`from_dict`."""
        out = {}
        for f in fields(self):
            v = getattr(self, f.name)
            if isinstance(v, (np.integer, np.floating)):
                v = v.item()
            out[f.name] = dict(v) if f.name == "extra" else v
        out["t_total"] = self.t_total
        return out

    @classmethod
    def from_dict(cls, d: dict) -> "JoinStats":
        names = {f.name for f in fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})


def _apply_verdicts(stats: JoinStats, verdicts: np.ndarray) -> None:
    stats.n_true_hits = int(np.sum(verdicts == TRUE_HIT))
    stats.n_true_negs = int(np.sum(verdicts == TRUE_NEG))
    stats.n_indecisive = int(np.sum(verdicts == INDECISIVE))


class JoinPlan:
    """A reusable two-dataset join session over one intermediate filter.

    ``filter`` is a registry name (``none/april/april-c/ri/ra/5cch``) or an
    :class:`IntermediateFilter` instance; ``filter_backend`` selects the
    verdict execution path of the intermediate-filter stage (``numpy`` |
    ``jnp`` | ``pallas`` | ``sequential``, DESIGN.md §9 — ``sequential``
    is the faithful per-pair reference every batched backend is
    verdict-identical to; ``backend`` is its historical alias, deprecated —
    passing it emits a ``DeprecationWarning``).
    ``r_kind``/``s_kind``
    mark a side as 'line' (open chains) for the linestring predicate.
    ``refine_backend`` selects the execution path of the final exact-geometry
    stage (``numpy`` | ``jnp`` | ``pallas`` | ``sequential``, DESIGN.md §7) —
    every backend is verdict-identical to the sequential per-pair reference.
    ``mbr_backend`` selects the execution path of candidate generation
    (``numpy`` | ``jnp`` | ``sequential``, DESIGN.md §8); ``mbr_grid`` pins
    the bucket granularity (default: adaptive from MBR-extent statistics) —
    neither changes the candidate pair set. ``build_opts`` go to
    ``filter.build`` (e.g. ``build_backend``, ``max_cells`` for RA,
    ``method`` for APRIL construction); ``filter_opts`` go to every
    ``filter.verdicts`` call (e.g. ``order`` for APRIL).
    ``pipeline_mode`` selects where stage boundaries live (DESIGN.md §12):
    ``staged`` (default) materializes each stage's survivors on host;
    ``fused`` chains the stages device-resident with one end-of-chain sync
    — result pairs and their order are identical either way.
    ``plan_mode`` selects who picks the configuration (DESIGN.md §13):
    ``static`` (default) executes the knobs above verbatim; ``adaptive``
    runs the sample-based cost planner on the first :meth:`execute` (or an
    explicit :meth:`plan` call) and adopts its choice of filter method,
    ``n_order``, join order, and pipeline mode. ``plan_opts`` tune the
    planner (see :data:`~repro.spatial.planner.PLAN_DEFAULTS`);
    ``plan_choice`` injects a pre-computed choice (per-shard plans, the
    service's replan cache) instead of re-sampling.
    """

    def __init__(self, R, S, *, filter: str | IntermediateFilter = "april",
                 filter_backend: str | None = None,
                 backend: str | None = None, refine_backend: str = "numpy",
                 mbr_backend: str = "numpy", n_order: int = 10,
                 extent: Extent = GLOBAL_EXTENT, r_kind: str = "polygon",
                 s_kind: str = "polygon", mbr_grid: int | None = None,
                 mbr_index: "MBRIndex | None" = None,
                 pipeline_mode: str = "staged",
                 plan_mode: str = "static",
                 plan_opts: dict | None = None,
                 plan_choice: PlanChoice | None = None,
                 build_opts: dict | None = None,
                 filter_opts: dict | None = None):
        if (filter_backend is not None and backend is not None
                and filter_backend != backend):
            raise ValueError("pass filter_backend or its alias backend, "
                             f"not both ({filter_backend!r} vs {backend!r})")
        if backend is not None:
            warnings.warn(
                "JoinPlan(backend=...) is a deprecated alias; "
                "pass filter_backend=... instead (alias removed after "
                "2026-12-01)",
                DeprecationWarning, stacklevel=2)
        filter_backend = filter_backend or backend or "numpy"
        check_filter_backend(filter_backend)
        refine._check_backend(refine_backend)
        _check_mbr_backend(mbr_backend)
        check_pipeline_mode(pipeline_mode)
        check_plan_mode(plan_mode)
        if plan_choice is not None and plan_mode != "adaptive":
            raise ValueError("plan_choice requires plan_mode='adaptive' "
                             f"(got plan_mode={plan_mode!r})")
        self.R = R
        self.S = S
        self.filter = get_filter(filter)
        self.filter_backend = filter_backend
        self.backend = filter_backend      # historical alias
        self.refine_backend = refine_backend
        self.mbr_backend = mbr_backend
        self.n_order = n_order
        self.extent = extent
        self.r_kind = r_kind
        self.s_kind = s_kind
        self.mbr_grid = mbr_grid
        self.mbr_index = mbr_index
        self.pipeline_mode = pipeline_mode
        self.plan_mode = plan_mode
        self.plan_opts = dict(plan_opts or {})
        self.plan_choice: PlanChoice | None = None
        self.build_opts = dict(build_opts or {})
        self.filter_opts = dict(filter_opts or {})
        self.approx_r: Approximation | None = None
        self.approx_s: Approximation | None = None
        self._t_build = 0.0
        self._t_plan = 0.0
        self.last_stats: JoinStats | None = None
        if plan_choice is not None:
            self._apply_choice(plan_choice)

    # -- preprocessing ------------------------------------------------------

    def _wrap(self, store, kind: str) -> Approximation:
        if isinstance(store, Approximation):
            return store
        return Approximation(filter=self.filter.name, store=store,
                             n_order=self.n_order, extent=self.extent,
                             kind=kind)

    def build(self, prebuilt: tuple | None = None) -> "JoinPlan":
        """Build (or adopt) both approximations; idempotent.

        ``prebuilt`` may supply an (approx_r, approx_s) tuple — raw stores
        are wrapped — with ``None`` entries meaning "build this side".
        """
        pre_r = pre_s = None
        if prebuilt is not None:
            pre_r, pre_s = prebuilt
        t0 = time.perf_counter()
        if self.approx_r is None:
            self.approx_r = (self._wrap(pre_r, self.r_kind)
                             if pre_r is not None else
                             self.filter.build(
                                 self.R, n_order=self.n_order,
                                 extent=self.extent, kind=self.r_kind,
                                 side="r", **self.build_opts))
        if self.approx_s is None:
            self.approx_s = (self._wrap(pre_s, self.s_kind)
                             if pre_s is not None else
                             self.filter.build(
                                 self.S, n_order=self.n_order,
                                 extent=self.extent, kind=self.s_kind,
                                 side="s", **self.build_opts))
        self._t_build += time.perf_counter() - t0
        return self

    # -- adaptive planning (DESIGN.md §13) ----------------------------------

    def _apply_choice(self, choice: PlanChoice) -> None:
        """Adopt a planner choice: swap filter/granularity/order/pipeline.
        Built approximations are invalidated when the store shape changes
        (a prebuilt store for the chosen config can still be adopted via
        :meth:`build`'s ``prebuilt``)."""
        if (choice.method != self.filter.name
                or int(choice.n_order) != self.n_order):
            self.approx_r = self.approx_s = None
        self.filter = get_filter(choice.method)
        self.n_order = int(choice.n_order)
        self.pipeline_mode = choice.pipeline_mode
        if (choice.method in ("april", "april-c")
                and choice.predicate in ("intersects", "selection")):
            self.filter_opts["order"] = tuple(choice.order)
        else:
            self.filter_opts.pop("order", None)
        self.plan_choice = choice

    def plan(self, predicate: str = "intersects",
             pairs: np.ndarray | None = None) -> PlanChoice:
        """Run the sample-based planner for ``predicate`` and apply its
        choice (``plan_mode='adaptive'`` only). Called lazily by the first
        :meth:`execute`; call explicitly to re-plan (e.g. after the
        workload drifts). ``pairs`` may supply the candidate set when the
        caller already generated it (the launcher's
        :class:`~repro.spatial.planner.ProfileCache` path keys on the
        candidate count before deciding whether to plan at all) — it must
        equal :meth:`candidates` (``predicate``) output. Deterministic for
        fixed inputs and ``plan_opts['seed']``."""
        if self.plan_mode != "adaptive":
            raise ValueError("plan() requires JoinPlan(plan_mode="
                             f"'adaptive'), got {self.plan_mode!r}")
        t0 = time.perf_counter()
        if pairs is None:
            pairs = self.candidates(predicate)
        choice = choose_plan(self.R, self.S, pairs, predicate=predicate,
                             n_order=self.n_order, extent=self.extent,
                             r_kind=self.r_kind, **self.plan_opts)
        self._t_plan = time.perf_counter() - t0
        self._apply_choice(choice)
        return choice

    # -- candidate generation (the MBR filter, per predicate) ---------------

    def candidates(self, predicate: str = "intersects") -> np.ndarray:
        """Candidate pairs through the §8 grid-hash join (``mbr_backend``).

        No predicate materializes the dense [N, M] cross test: ``within``
        needs MBR *containment*, but containment implies intersection, so
        the (stricter) containment test runs on just the hash join's
        candidate rows.

        A warm :class:`~repro.spatial.mbr_join.MBRIndex` over R
        (``mbr_index``) replaces the per-call expansion + sort of the R
        side with a probe against its prebuilt bucket table — the pair set
        is identical either way (grid/extent invariance).
        """
        R, S = self.R, self.S
        if self.mbr_index is not None:
            pairs = self.mbr_index.probe(S.mbrs, backend=self.mbr_backend)
        else:
            pairs = mbr_join(R.mbrs, S.mbrs, grid=self.mbr_grid,
                             backend=self.mbr_backend)
        if predicate == "within":
            mr = R.mbrs[pairs[:, 0]]
            ms = S.mbrs[pairs[:, 1]]
            inside = ((mr[:, 0] >= ms[:, 0]) & (mr[:, 1] >= ms[:, 1])
                      & (mr[:, 2] <= ms[:, 2]) & (mr[:, 3] <= ms[:, 3]))
            return pairs[inside]
        return pairs

    # -- execution ----------------------------------------------------------

    def _refine(self, predicate: str, pairs: np.ndarray) -> np.ndarray:
        if len(pairs) == 0:
            return np.zeros(0, bool)
        return refine.refine(self.R, self.S, pairs, predicate=predicate,
                             backend=self.refine_backend)

    def execute(self, predicate: str = "intersects",
                ) -> tuple[np.ndarray, JoinStats]:
        """Run MBR -> filter -> refine; returns (result pairs [K,2], stats).

        For ``selection``, result rows are (data index, query index) — see
        :func:`repro.spatial.pipeline.selection_queries` for the per-query
        grouping wrapper.

        The run is one trace block (:mod:`repro.runtime.trace`) under the
        span ``repro.join``; ``stats.extra`` reports its routed rows
        (``routed``), counters (``counters``) and host seconds per span
        (``spans_s``).
        """
        if predicate == "linestring" and self.r_kind != "line":
            raise ValueError("predicate 'linestring' needs JoinPlan(..., "
                             "r_kind='line') with the chains as R")
        if predicate != "linestring" and self.r_kind == "line":
            raise ValueError(
                f"predicate {predicate!r} needs polygon approximations, but "
                "this plan was built with r_kind='line'")
        if self.plan_mode == "adaptive" and self.plan_choice is None:
            self.plan(predicate)
        if self.approx_r is None or self.approx_s is None:
            self.build()
        stats = JoinStats(method=self.filter.name, predicate=predicate,
                          backend=self.filter_backend,
                          filter_backend=self.filter_backend,
                          refine_backend=self.refine_backend,
                          mbr_backend=self.mbr_backend,
                          pipeline_mode=self.pipeline_mode,
                          plan_mode=self.plan_mode)
        if self.plan_choice is not None:
            stats.extra["plan"] = self.plan_choice.to_dict()
            stats.extra["t_plan"] = self._t_plan
        stats.t_build = self._t_build
        stats.approx_bytes = (self.approx_r.size_bytes()
                              + self.approx_s.size_bytes())

        with trace_block() as block:
            with span("repro.join"):
                results, stats = self._execute(predicate, stats)
        spans = block.spans_s
        stats.t_mbr = spans.get("repro.mbr", 0.0)
        stats.t_filter = spans.get("repro.filter", 0.0)
        stats.t_refine = spans.get("repro.refine", 0.0)
        stats.t_sync = (spans.get("repro.sync.gather", 0.0)
                        + spans.get("repro.sync.escalate", 0.0))
        stats.extra["routed"] = block.routed
        stats.extra["counters"] = block.counters
        stats.extra["spans_s"] = spans
        self.last_stats = stats
        return results, stats

    def _execute(self, predicate: str, stats: JoinStats):
        if self.pipeline_mode == "fused":
            return execute_fused(self, predicate, stats)

        with span("repro.mbr"):
            pairs = self.candidates(predicate)
        stats.n_candidates = len(pairs)
        if len(pairs) == 0:
            return np.zeros((0, 2), np.int64), stats

        with span("repro.filter"):
            verdicts = self.filter.verdicts(
                self.approx_r, self.approx_s, pairs, predicate=predicate,
                backend=self.filter_backend, **self.filter_opts)
        _apply_verdicts(stats, verdicts)

        with span("repro.refine"):
            indec = pairs[verdicts == INDECISIVE]
            ref = self._refine(predicate, indec)

        results = np.concatenate([pairs[verdicts == TRUE_HIT], indec[ref]],
                                 axis=0)
        stats.n_results = len(results)
        return results, stats
