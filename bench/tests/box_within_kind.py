"""A traffic kind for the tests alone, whose answer is not the intersects
pair set: the pairs (r, s) whose box r lies inside box s. It brings its
own data, reference and check through the driver contract
(``harness/mixes.py``), and :func:`install` reaches it through a
patched ``mixes.make``, so no file of the harness names it.

Half of the R boxes lie inside the S box of the same index; the other
half stick out of it by ``GAP`` on one side, which float32 coordinates
cannot tell from lying inside it: the float32 control finds those pairs
too, and comes out not correct.
"""
from __future__ import annotations

import time

import numpy as np

KIND = "box_within"
#: how far a box that is not inside sticks out, in map units (float32
#: spacing near 0.5 is 6e-8)
GAP = 1e-11


def boxes(n: int, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """R and S boxes [n, 4] (x0, y0, x1, y1) from ``seed``."""
    rng = np.random.default_rng(seed)
    lo = rng.uniform(0.3, 0.5, (n, 2))
    s = np.concatenate([lo, lo + rng.uniform(0.05, 0.2, (n, 2))], axis=1)
    # S corners float32 can hold, so float32 rounds a corner GAP away
    # onto the corner itself
    s = s.astype(np.float32).astype(np.float64)
    inset = rng.uniform(0.0, 0.02, (n, 4)) * [1, 1, -1, -1]
    r = s + inset
    out = np.arange(1, n, 2)
    side = rng.integers(0, 4, len(out))
    r[out, side] = s[out, side] + np.where(side < 2, -GAP, GAP)
    return r, s


def within_pairs(r: np.ndarray, s: np.ndarray, dtype=np.float64):
    """Every (r id, s id) whose box r lies inside box s, in ``dtype``."""
    r, s = r.astype(dtype), s.astype(dtype)
    lo, hi = r[:, None, :2], r[:, None, 2:]
    inside = ((s[None, :, :2] <= lo) & (hi <= s[None, :, 2:])).all(axis=2)
    return np.stack(np.nonzero(inside), axis=1)


class Driver:
    """Repeated containment queries over one pair of box layers."""

    CHECKS = ("wrong_within_pairs",)

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.n = int(config["boxes"])
        self.seed = int(seed)
        self.geo: dict = {}
        self.results: list[np.ndarray] = []
        self.failed = 0
        self.error = ""

    def data(self, seed: int) -> dict:
        r, s = boxes(self.n, seed)
        return {"r": r, "s": s}

    def reference(self, mode: str) -> np.ndarray:
        dtype = np.float32 if mode == "f32" else np.float64
        return within_pairs(self.geo["r"], self.geo["s"], dtype)

    def setup(self, span) -> dict:
        self.geo = self.data(self.seed)
        return {}

    def window(self, seconds: float, span) -> dict:
        t0 = time.perf_counter()
        while True:
            with span("bench.query"):
                self.results.append(within_pairs(self.geo["r"],
                                                 self.geo["s"]))
            if time.perf_counter() - t0 >= seconds:
                break
        return {"query_s": (time.perf_counter() - t0)
                / max(1, len(self.results))}

    @property
    def attempted(self) -> int:
        return len(self.results) + self.failed

    def layer_inputs(self) -> dict:
        return {"units": len(self.results)}

    def release(self) -> None:
        pass

    def check(self) -> dict:
        want = {tuple(p) for p in self.reference("exact").tolist()}
        wrong = sum(len(want ^ {tuple(p) for p in got.tolist()})
                    for got in self.results)
        return {"wrong_within_pairs": (wrong, 0)}


def cell(spec):
    """A cell of this kind, with one end-to-end metric besides
    ``setup_s`` and one per-layer metric."""
    return spec.Cell(
        name="boxes.within", chips=1, config={"boxes": 256},
        traffic={"kind": KIND},
        end_to_end=[{"name": "setup_s", "unit": "s"},
                    {"name": "query_s", "unit": "s"}],
        per_layer=[{"name": "within.queries", "unit": "queries"}],
        readers={"within.queries": lambda ctx: ctx["units"]})


def install(monkeypatch, mixes) -> None:
    """Make ``mixes.make`` build this kind's driver for ``KIND``."""
    make = mixes.make

    def patched(kind, config, traffic, seed):
        if kind == KIND:
            return Driver(config, traffic, seed)
        return make(kind, config, traffic, seed)

    monkeypatch.setattr(mixes, "make", patched)
