"""One run of one cell: set-up, the measured window, the readings and the
comparison that decides ``correct``.

:func:`run` is what ``bench/run.py`` calls once it has found the chips;
the tests call it on the CPU at a tiny size.
"""
from __future__ import annotations

import contextlib
import json
import time
from pathlib import Path

from . import mixes, spec, trace

#: compile events counted in set-up and in the window
_COMPILE = "/jax/core/compile/backend_compile_duration"
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class _Counter:
    """Programs compiled, loaded from the persistent cache, and functions
    traced while it listens."""

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0
        self.traces = 0

    def duration(self, event: str, duration: float, **kw) -> None:
        if event == _COMPILE:
            self.compiles += 1
        elif event == _TRACE:
            self.traces += 1

    def event(self, event: str, **kw) -> None:
        if event == _CACHE_HIT:
            self.cache_hits += 1

    def __str__(self) -> str:
        return (f"compiles={self.compiles} cache_hits={self.cache_hits} "
                f"traces={self.traces}")


@contextlib.contextmanager
def counting():
    import jax
    c = _Counter()
    jax.monitoring.register_event_duration_secs_listener(c.duration)
    jax.monitoring.register_event_listener(c.event)
    try:
        yield c
    finally:
        jax.monitoring.unregister_event_duration_listener(c.duration)
        jax.monitoring.unregister_event_listener(c.event)


def _peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _layer_context(cell, drv, devices, trace_dir: Path, peaks):
    """What the per-layer readers read: the mix's counts, and the
    device time of the traced window by stage. The window is the
    ``bench.window`` span of the trace."""
    ctx = dict(drv.layer_inputs())
    per_device, spans = trace.load(str(trace_dir))
    window = [s for s in spans if s[0] == "bench.window"]
    if not window:
        raise RuntimeError("the trace holds no bench.window span")
    lo, hi = window[-1][1], window[-1][2]
    stages = spec.load_json(spec.BENCH / "stages.json")["stages"]
    ops = [op for d in devices for op in per_device.get(
        f"/device:TPU:{d.id}", [])]
    ops = [(n, s, e, p) for n, s, e, p in ops if e > lo and s < hi]
    clipped = [(n, max(s, lo), min(e, hi), p) for n, s, e, p in ops]
    n_dev = max(1, len(devices))
    ctx.update({
        "kind": cell.traffic["kind"],
        "window_s": (hi - lo) / 1e9,
        "busy_s": trace.union_ns([(s, e) for _, s, e, _ in clipped])
        / 1e9 / n_dev,
        "stage_s": {k: v / 1e9 / n_dev
                    for k, v in trace.stage_ns(clipped, stages).items()},
        "peak": peaks,
        "breakdown": {
            "device_ops": trace.top_ops(clipped, 10),
            "idle_gaps": trace.idle_gaps(clipped, spans, lo, hi, 10)},
    })
    return ctx


def peaks_for(kind: str) -> dict:
    table = spec.load_json(spec.BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool,
        t_start: float, devices, trace_dir: Path, log) -> dict:
    """One run; returns the result object the last line prints."""
    import jax
    dev = devices[0]
    peaks = peaks_for(dev.device_kind) if traced else None
    drv = mixes.make(cell.traffic["kind"], cell.config, cell.traffic,
                       seed)
    span = (jax.profiler.TraceAnnotation if traced
            else lambda name: contextlib.nullcontext())
    with counting() as c_setup:
        e2e = drv.setup(span)
    e2e["setup_s"] = time.perf_counter() - t_start
    log("setup: " + ", ".join(f"{k} {v:.4f}" for k, v in e2e.items())
        + f", {c_setup}")
    if traced:
        trace_dir.mkdir(parents=True, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        with counting() as c_win, span("bench.window"):
            e2e.update(drv.window(seconds, span))
    finally:
        if traced:
            jax.profiler.stop_trace()
    log(f"window: {drv.attempted} attempted, {drv.failed} failed, "
        f"{c_win}")
    if drv.error:
        log(f"error: {drv.error}")
    peak = _peak_bytes(devices)

    metrics, breakdown, device = {}, None, {}
    if traced:
        ctx = _layer_context(cell, drv, devices, trace_dir, peaks)
        log("layer inputs: " + json.dumps(
            {k: v for k, v in ctx.items() if isinstance(v, (int, float))}))
        log("stage device s: " + json.dumps(ctx["stage_s"]))
        for m in cell.per_layer:
            value = cell.readers[m["name"]](ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = ctx["breakdown"]
        device = {"busy_s": ctx["busy_s"], "window_s": ctx["window_s"]}
    else:
        for m in cell.end_to_end:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    drv.release()

    checks = drv.check()
    correct = all(v <= lim for v, lim in checks.values())
    result = {
        "correct": bool(correct),
        "attempted": drv.attempted,
        "failed": drv.failed,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": peak,
                   **device},
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result
