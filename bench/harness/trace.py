"""Reduction of a profiler trace to device busy time, stage times and the
breakdown of a traced window.

The reduction works on plain event tuples, so it can be checked on a
small hand-made event list. :func:`load` turns the ``.xplane.pb`` that
``jax.profiler`` writes into those tuples:

* device ops: ``(name, start_ns, end_ns, program)`` from the ``XLA Ops``
  line of each TPU device plane, ``program`` being the jitted program
  (HLO module) the op belongs to;
* host spans: ``(name, start_ns, end_ns)`` of the ``TraceAnnotation``
  spans from the host plane: the benchmark's own (``bench.*``) and the
  program's (``repro.*``, opened by ``repro.runtime.trace.span``), so an
  idle gap is named by the program step the host was in.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

__all__ = ["union_ns", "clip", "stage_ns", "top_ops", "idle_gaps", "load"]

#: prefixes of the host events :func:`load` keeps as spans
SPAN_PREFIXES = ("bench.", "repro.")


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of ``(start, end)`` intervals inside [lo, hi]."""
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def union_ns(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def stage_of(program: str, stages: dict) -> str:
    """The stage whose first matching pattern (``re.search``) names
    ``program``; ``other`` where none does."""
    for stage, patterns in stages.items():
        if any(re.search(p, program) for p in patterns):
            return stage
    return "other"


def stage_ns(ops, stages: dict) -> dict[str, float]:
    """Busy time per stage: the union of the intervals of the ops whose
    program maps to that stage."""
    by: dict[str, list] = {}
    for _, s, e, program in ops:
        by.setdefault(stage_of(program, stages), []).append((s, e))
    return {st: union_ns(iv) for st, iv in by.items()}


def op_label(name: str, program: str) -> str:
    """``<program>/<op>``: the program without its fingerprint, and the
    op's HLO name without its operands."""
    op = name.split(" = ")[0].lstrip("%")
    return f"{re.sub(r'[(][0-9]+[)]$', '', program)}/{op}"


def top_ops(ops, n: int = 10) -> list[list]:
    """The ``n`` ops (by :func:`op_label`) that took most device time:
    [[label, seconds]]."""
    tot: dict[str, float] = {}
    for name, s, e, program in ops:
        label = op_label(name, program)
        tot[label] = tot.get(label, 0.0) + (e - s)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def idle_gaps(ops, spans, lo: float, hi: float, n: int = 10) -> list[list]:
    """The ``n`` longest stretches of [lo, hi] in which no op ran, each
    named by the innermost host span open across at least half of it (or
    else the span that covers most of it): [[span name, seconds]]."""
    busy = sorted(clip([(s, e) for _, s, e, _ in ops], lo, hi))
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    out = []
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        cover = [(min(e, g1) - max(s, g0), e - s, name)
                 for name, s, e in spans]
        most = [c for c in cover if 2 * c[0] >= g1 - g0]
        if most:        # the innermost span open across most of the gap
            name = min(most, key=lambda c: c[1])[2]
        else:
            best = max(cover, default=(0, 0, "no span"))
            name = best[2] if best[0] > 0 else "no span"
        out.append([name, (g1 - g0) / 1e9])
    return out


def _enclosing(modules, starts, t: float) -> str:
    """Name of the module event open at time ``t``; ``modules`` are
    (name, start, end) sorted by start, ``starts`` their starts."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < modules[i][2]:
        return modules[i][0]
    return ""


def load(trace_dir: str):
    """(device ops per TPU plane {plane: [ops]}, host spans) from the
    newest ``.xplane.pb`` under ``trace_dir``. Host spans are the events
    whose name starts with one of ``SPAN_PREFIXES``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name not in ("XLA Ops", "XLA Modules"):
                    continue
                for ev in line.events:
                    iv = (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    if line.name == "XLA Modules":
                        modules.append(iv)
                    else:
                        st = dict(ev.stats)
                        ops.append(iv + (str(st.get("hlo_module", "")),))
            modules.sort(key=lambda m: m[1])
            starts = [s for _, s, _ in modules]
            devices[plane.name] = [
                op if op[3] else op[:3] + (_enclosing(modules, starts,
                                                      op[1]),)
                for op in ops]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    return devices, spans
