#!/usr/bin/env python3
"""The chip benchmark of the spatial-join system: one run of one cell.

    python3 bench/run.py --workload t1t2.join --seed 7 --seconds 10 --trace 0

Run from the root of a checkout on a machine with TPU chips. The cell is
found by name in ``BENCHMARK.json``; its configuration, traffic mix and
per-layer metric readers are files under ``bench/`` (``harness/spec.py``).
The run generates its data from ``--seed``, sets up and warms every
program the window uses (``setup_s``), measures for ``--seconds``, then
compares what the window produced with the plain reference.

With ``--trace 0`` the last line of standard output carries the cell's
end-to-end metrics; with ``--trace 1`` the window runs under the profiler
and the line carries the per-layer metrics, the device's busy time and a
breakdown. The numbers compared for ``correct`` are printed last on
standard error, and last in the result line under ``checks``.

The run never falls back to the CPU: without a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result. JAX's
persistent compilation cache lives at ``<checkout>/.jax_cache`` unless
``JAX_COMPILATION_CACHE_DIR`` names another directory.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"
CACHE_DIR = ROOT / ".jax_cache"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _entries(path: str) -> int:
    p = Path(path)
    return sum(1 for _ in p.iterdir()) if p.is_dir() else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(BENCH))
    from harness import runner, spec
    try:
        cell = spec.resolve(args.workload, ROOT)
    except (OSError, KeyError, ValueError) as e:
        log(f"bench/run.py: {e}")
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        log("bench/run.py: the program (src/repro) is not in this checkout")
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"bench/run.py: needs a TPU, JAX's first device is "
            f"{devices[0].platform!r}")
        return 3
    if len(devices) < cell.chips:
        log(f"bench/run.py: {cell.name} needs {cell.chips} chips, found "
            f"{len(devices)}")
        return 3
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(CACHE_DIR)
        CACHE_DIR.mkdir(exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache every program, the small ones too, so a warm set-up compiles
    # nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    log(f"device: {devices[0].device_kind} x{len(devices)}; cell "
        f"{cell.name}, seed {args.seed}, {args.seconds} s, "
        f"trace {args.trace}; compile cache {cache_dir} "
        f"({_entries(cache_dir)} entries)")

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    result = runner.run(cell, args.seed, args.seconds, bool(args.trace),
                        T_START, devices[:cell.chips], TRACE_DIR, log)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    log(f"compile cache: {_entries(cache_dir)} entries")
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
