"""Pallas TPU kernels for the performance-critical layers.

Each kernel package ships three files:
  <name>.py — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (padding, dtype plumbing, interpret flag)
  ref.py    — pure-jnp oracle used by the allclose test sweeps

Kernels are validated on CPU with ``interpret=True`` and designed for the
TPU memory hierarchy (HBM->VMEM tiles, (8,128) VPU lanes, MXU-aligned dims).

This module also holds the device-path policy shared by every caller:
:func:`interpret_mode` decides where kernels interpret, and the *routed-row
counts* record every row the device path hands elsewhere — interval rows
wider than a kernel tile admits (to the host), lanes longer than one
compaction launch (to jnp), and guard-band pairs re-checked at host f64.
``JoinPlan.execute`` counts each run under :func:`count_routed` and
reports the counts in ``JoinStats.extra["routed"]``, and the launcher's
``run_join``/``run_tiled_join`` count and print theirs (mesh paths
included), so no cut-off is silent.
"""
import contextlib
import contextvars

import numpy as np

#: routed-row count names (see :func:`note_routed`)
ROUTED_KEYS = ("filter_wide_rows_host", "compact_long_lane_rows_jnp",
               "refine_escalated_rows_host")

#: the counts of the innermost :func:`count_routed` block of this thread
#: (or task); None outside any block
_ROUTED: contextvars.ContextVar = contextvars.ContextVar("routed_rows",
                                                         default=None)


def interpret_mode() -> bool:
    """Whether the Pallas kernels run interpreted: the one place that
    decides it. Off a TPU (the CPU test runs) the kernels must interpret;
    on a TPU they always compile through Mosaic — there is no interpreted
    fallback on the chip."""
    import jax
    return jax.devices()[0].platform != "tpu"


def pad_rows_pow2(xs: list[np.ndarray], multiple: int = 1
                  ) -> tuple[list[np.ndarray], int]:
    """Zero-pad equal-length host arrays (along axis 0) to the next power
    of two (then up to ``multiple``) so jitted consumers — kernels above
    all — recompile logarithmically in the row count, not per shape;
    returns (padded arrays, original length). Zero rows carry zero counts
    or masks, so every kernel reads them as non-results."""
    n = len(xs[0])
    p2 = 1 << int(np.ceil(np.log2(max(n, 1))))
    pad = max(multiple, ((p2 + multiple - 1) // multiple) * multiple)
    return [x if len(x) == pad else
            np.concatenate([x, np.zeros((pad - n,) + x.shape[1:], x.dtype)])
            for x in xs], n


@contextlib.contextmanager
def count_routed():
    """Count the rows routed off the device path inside the block; yields
    the ``{name: rows}`` dict it fills. Blocks nest — an inner block's
    counts also add to the enclosing block's — and each thread counts its
    own work."""
    outer = _ROUTED.get()
    counts = dict.fromkeys(ROUTED_KEYS, 0)
    token = _ROUTED.set(counts)
    try:
        yield counts
    finally:
        _ROUTED.reset(token)
        if outer is not None:
            for key, n in counts.items():
                outer[key] += n


def note_routed(key: str, n: int) -> None:
    """Add ``n`` rows to routed-row count ``key`` of the current
    :func:`count_routed` block (a no-op outside one)."""
    counts = _ROUTED.get()
    if counts is not None and n:
        counts[key] += int(n)
