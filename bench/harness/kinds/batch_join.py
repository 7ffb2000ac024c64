"""Traffic kind ``batch_join`` (parameter: ``predicate``): one
``JoinPlan`` is built over the configuration's two layers, and the window
runs ``JoinPlan.execute(predicate)`` on it again and again. Each join
runs candidate generation, the filter and refinement, and hands the exact
result pairs to the host; nothing of an earlier join is reused but the
built approximations, which users build once per dataset version.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from .. import datagen, reference, work

#: set-up builds the approximations back to back for this long, after one
#: build that warms; ``build_s`` is that time over the builds made in it
BUILD_SECONDS = 8.0


class Driver:
    """Repeated whole joins of two built layers."""

    CHECKS = ("missing_pairs", "extra_pairs", "repeated_pairs",
              "failed_joins")

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config = config
        self.predicate = traffic["predicate"]
        self.seed = int(seed)
        self.geo: dict = {}
        self.plan = None
        self.results: list[np.ndarray] = []
        self.stats: list = []
        self.failed = 0
        self.error = ""

    # -- data and the plain reference ----------------------------------------

    def data(self, seed: int) -> dict:
        """Both layers and their near misses (:func:`datagen.deployment`)."""
        return datagen.deployment(self.config, seed)

    def reference(self, mode: str) -> np.ndarray:
        """The reference's ``intersects`` pairs over ``self.geo``, in
        ``mode`` (``exact`` or ``f32``)."""
        return reference.pairs(self.geo["r"], self.geo["s"], mode)

    # -- set-up -------------------------------------------------------------

    def _new_plan(self):
        from repro.datagen.synthetic import PolygonDataset
        from repro.spatial import JoinPlan
        R = PolygonDataset("R", self.geo["r"][0], self.geo["r"][1])
        S = PolygonDataset("S", self.geo["s"][0], self.geo["s"][1])
        return JoinPlan(R, S, n_order=datagen.scaled_order(
            float(self.config["k"])), **self.config["plan"])

    def setup(self, span) -> dict:
        """Generate both layers, build their approximations once to warm
        and then back to back for ``BUILD_SECONDS`` (``build_s``), and run
        one warm join, which compiles or loads every program the window
        uses. Returns the set-up's end-to-end readings."""
        self.geo = self.data(self.seed)
        with span("bench.build_first"):
            self._new_plan().build()
        builds, t0 = 0, time.perf_counter()
        while True:
            plan = self._new_plan()
            with span("bench.build"):
                plan.build()
            builds += 1
            if time.perf_counter() - t0 >= BUILD_SECONDS:
                break
        build_s = (time.perf_counter() - t0) / builds
        with span("bench.warm_join"):
            plan.execute(self.predicate)
        self.plan = plan
        return {"build_s": build_s}

    # -- the measured window --------------------------------------------------

    def window(self, seconds: float, span) -> dict:
        """Run joins back to back until ``seconds`` have passed; the join
        under way at the close completes and counts. ``join_s`` is all
        the window's time over all joins completed in it."""
        t0 = time.perf_counter()
        while True:
            try:
                with span("bench.join"):
                    pairs, st = self.plan.execute(self.predicate)
            except Exception as e:  # a join that raises is failed work
                self.failed += 1
                self.error = f"{type(e).__name__}: {e}"
                break
            self.results.append(pairs)
            self.stats.append(st)
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        return {"join_s": elapsed / max(1, len(self.results))}

    @property
    def attempted(self) -> int:
        return len(self.results) + self.failed

    # -- what the per-layer readers read ------------------------------------

    def layer_inputs(self) -> dict:
        """Counts of the window's joins and the work one join needs."""
        lists = []
        for approx in (self.plan.approx_r, self.plan.approx_s):
            st = approx.store
            lists.append((np.diff(st.a_off), np.diff(st.f_off)))
        deg_r, deg_s = work.mbr_degrees(self.geo["r"][2], self.geo["s"][2])
        return {"units": len(self.results),
                "stats": [s.to_dict() for s in self.stats],
                "mbr_candidates": int(deg_r.sum()),
                "filter_bytes": work.filter_bytes(lists[0], lists[1],
                                                  deg_r, deg_s),
                "filter_comparisons": work.filter_comparisons(
                    lists[0], lists[1], deg_r, deg_s)}

    def release(self) -> None:
        """Drop the program's state, device arrays included."""
        self.plan = None
        gc.collect()

    # -- correctness ----------------------------------------------------------

    def check(self) -> dict:
        """Compare the whole pair set of every join of the window with
        the float64 reference over every R object: {name: (value,
        limit)}."""
        want = self.reference("exact")
        n_s = len(self.geo["s"][1])
        bad = {"missing_pairs": 0, "extra_pairs": 0, "repeated_pairs": 0}
        for pairs in self.results:
            c = reference.compare(pairs, want, n_s)
            bad["missing_pairs"] += c["missing"]
            bad["extra_pairs"] += c["extra"]
            bad["repeated_pairs"] += c["repeated"]
        checks = {k: (v, 0) for k, v in bad.items()}
        checks["failed_joins"] = (self.failed, 0)
        return checks
