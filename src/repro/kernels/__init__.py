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
The counts live in the trace blocks of :mod:`repro.runtime.trace`,
re-exported here: ``JoinPlan.execute`` counts each run in one block and
reports the counts in ``JoinStats.extra["routed"]``, and the launcher's
``run_join``/``run_tiled_join`` count and print theirs (mesh paths
included), so no cut-off is silent. Uploads to the device go through
:func:`to_device`, which counts their bytes in the same block.
"""
import jax.numpy as jnp
import numpy as np

from ..runtime.trace import (ROUTED_KEYS, count, count_routed,  # noqa: F401
                             note_routed)


def interpret_mode() -> bool:
    """Whether the Pallas kernels run interpreted: the one place that
    decides it. Off a TPU (the CPU test runs) the kernels must interpret;
    on a TPU they always compile through Mosaic — there is no interpreted
    fallback on the chip."""
    import jax
    return jax.devices()[0].platform != "tpu"


def to_device(x):
    """The host -> device upload of one host array: the device copy, its
    bytes counted as ``h2d_bytes`` in the current trace block. Dtypes
    follow ``jnp.asarray`` (so call it under ``jax.enable_x64`` where a
    64-bit array must stay 64-bit)."""
    out = jnp.asarray(x)
    count("h2d_bytes", out.nbytes)
    return out


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
