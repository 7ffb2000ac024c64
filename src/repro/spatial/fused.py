"""The fused device-resident pipeline (DESIGN.md §12).

``JoinPlan(pipeline_mode="fused")`` runs MBR -> intermediate filter ->
refinement as ONE dispatch chain: every stage consumes and produces a
:class:`CandidateSet` — a host-known pair frame plus device-resident status
lanes — and stage boundaries compact on device through
``kernels.compact.compact_mask`` instead of the staged mode's
materialize-compact-reupload round trips. Nothing returns to the host until
the single sanctioned :func:`to_host` gather at the end of the chain, which
also drives the one permitted host round trip: f64 re-refinement of the
FMA-borderline pairs the device refinement flagged uncertain.

Contract with the staged mode (the reference): identical result pairs, in
identical order, for every filter method, predicate, and backend — asserted
by tests/test_fused_pipeline.py. The staged per-stage backends remain the
references; fused changes *where* stage boundaries live, never verdicts.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import numpy as np

from ..core.join import INDECISIVE, TRUE_HIT, TRUE_NEG
from ..kernels import note_routed, pad_rows_pow2, to_device
from ..runtime.trace import count, span

__all__ = [
    "PIPELINE_MODES", "check_pipeline_mode", "to_host",
    "CandidateSet", "Stage", "StagePlan", "build_stage_plan",
    "execute_fused",
]

#: execution modes of JoinPlan (DESIGN.md §12): 'staged' materializes each
#: stage's survivors on host (the reference), 'fused' keeps the chain
#: device-resident with one end-of-chain sync
PIPELINE_MODES = ("staged", "fused")


def check_pipeline_mode(mode: str) -> None:
    if mode not in PIPELINE_MODES:
        raise ValueError(f"unknown pipeline_mode {mode!r}; "
                         f"expected one of {PIPELINE_MODES}")


def to_host(*vals):
    """The chain's single sanctioned device -> host materialization.

    Every lane of the finished chain gathers in ONE ``jax.device_get`` —
    the lexical choke point the HS001 static pass holds the fused pipeline
    to (staged reference paths route their per-stage pulls through here
    too, so intent stays visible). Returns numpy arrays, one per operand.
    Counts one ``syncs`` and the gathered ``d2h_bytes`` in the current
    trace block.
    """
    import jax
    got = jax.device_get(list(vals))  # analyze: ignore[HS001] the one sanctioned sync (DESIGN.md §12)
    count("syncs")
    count("d2h_bytes", sum(getattr(g, "nbytes", 0) for g in got))
    return got[0] if len(vals) == 1 else tuple(got)


# ---------------------------------------------------------------------------
# The stage contract
# ---------------------------------------------------------------------------

@dataclass
class CandidateSet:
    """The device-resident currency of the fused chain.

    The pair *frame* ``(ri, si)`` is host-known metadata — it comes out of
    grid-hash preprocessing over host MBR tables, so holding it costs no
    device sync. Everything data-dependent lives in device lanes over that
    frame: ``valid`` (MBR mask + ownership; ``None`` means the frame is
    pre-filtered on host and all rows are real), ``status`` (the int8
    trichotomy, already masked — invalid rows are TRUE_NEG), ``hit`` /
    ``unc`` (refined verdicts and FMA-borderline flags). Stages consume and
    produce CandidateSets; no stage materializes a lane.
    """
    ri: np.ndarray                 # [N] int64 host frame, R indices
    si: np.ndarray                 # [N] int64 host frame, S indices
    valid: object | None = None    # [N] device bool (None = all valid)
    status: object | None = None   # [N] device int8 trichotomy
    hit: object | None = None      # [N] device bool refined verdicts
    unc: object | None = None      # [N] device bool FMA-borderline

    def __len__(self) -> int:
        return len(self.ri)


@dataclass
class Stage:
    """One link of the chain; ``name`` keys its span (``repro.<name>``)
    and the JoinStats host-time field (``t_mbr`` / ``t_filter`` /
    ``t_refine``)."""
    name: str
    fn: Callable


class StagePlan:
    """An ordered CandidateSet -> CandidateSet chain, dispatched back to
    back with no intermediate host syncs.

    Each stage runs in the span ``repro.<name>``, whose host seconds
    ``JoinPlan.execute`` reports as ``stats.t_<name>``. They cover host
    work and *dispatch* only — JAX dispatch is asynchronous, so the device
    work of the whole chain surfaces in the end-of-chain gather, reported
    as ``t_sync``.
    """

    def __init__(self, stages: list[Stage]):
        self.stages = list(stages)

    def run(self, cs: CandidateSet | None = None) -> CandidateSet:
        for st in self.stages:
            with span("repro." + st.name):
                cs = st.fn(cs)
        return cs


def _empty_cs():
    import jax.numpy as jnp
    z = np.zeros(0, np.int64)
    return CandidateSet(ri=z, si=z, valid=None,
                        status=jnp.zeros(0, jnp.int8),
                        hit=jnp.zeros(0, bool), unc=jnp.zeros(0, bool))


# ---------------------------------------------------------------------------
# Stage builders
# ---------------------------------------------------------------------------

def build_stage_plan(plan, predicate: str) -> StagePlan:
    """The three-stage fused chain for one JoinPlan execution.

    * ``mbr`` — host grid-hash preprocessing producing the pair frame; with
      ``mbr_backend='jnp'`` the intersection + ownership mask stays a
      device ``valid`` lane (``pair_mask_lane_jnp``), the within MBR
      containment restriction folded in. A warm ``mbr_index`` or a host
      backend yields a pre-filtered frame (pure host work — no sync).
    * ``filter`` — the method's ``status_lane`` over the frame, masked so
      invalid rows read TRUE_NEG.
    * ``refine`` — on-device compaction of the INDECISIVE lane
      (``compact_mask``) + chunked packed refinement
      (``fused_refine_lanes``), scattered back to frame lanes.
    """
    import jax.numpy as jnp

    def mbr_stage(_):
        from .mbr_join import _prepare, candidate_rows, pair_mask_lane_jnp
        R, S = plan.R, plan.S
        if plan.mbr_index is not None or plan.mbr_backend != "jnp":
            with span("repro.mbr.frame"):
                pairs = plan.candidates(predicate)
            if len(pairs) == 0:
                return _empty_cs()
            return CandidateSet(ri=pairs[:, 0], si=pairs[:, 1])
        with span("repro.mbr.frame"):
            mbrs_r, mbrs_s, k, extent = _prepare(R.mbrs, S.mbrs,
                                                 plan.mbr_grid)
            if k == 0:
                return _empty_cs()
            ri, si, own_x, own_y, lo_r, lo_s = candidate_rows(
                mbrs_r, mbrs_s, k, extent)
        if len(ri) == 0:
            return _empty_cs()
        with span("repro.mbr.mask"):
            lane, n = pair_mask_lane_jnp(mbrs_r, mbrs_s, lo_r, lo_s,
                                         ri, si, own_x, own_y)
            valid = lane[:n]
            if predicate == "within":
                # the stricter containment restriction of
                # JoinPlan.candidates, evaluated on the host MBR tables
                # and folded into the lane
                mr, ms = mbrs_r[ri], mbrs_s[si]
                inside = ((mr[:, 0] >= ms[:, 0]) & (mr[:, 1] >= ms[:, 1])
                          & (mr[:, 2] <= ms[:, 2])
                          & (mr[:, 3] <= ms[:, 3]))
                valid = valid & to_device(inside)
        return CandidateSet(ri=ri, si=si, valid=valid)

    def filter_stage(cs):
        if len(cs) == 0:
            return cs
        lane = plan.filter.status_lane(
            plan.approx_r, plan.approx_s, cs.ri, cs.si,
            predicate=predicate, backend=plan.filter_backend,
            **plan.filter_opts)
        if cs.valid is not None:
            lane = jnp.where(cs.valid, lane, jnp.int8(TRUE_NEG))
        cs.status = lane
        return cs

    def refine_stage(cs):
        from . import refine as RF
        if len(cs) == 0:
            return cs
        from ..kernels.compact import compact_mask
        cb = "pallas" if plan.refine_backend == "pallas" else "jnp"
        with span("repro.refine.compact"):
            perm, n_indec = compact_mask(cs.status == INDECISIVE, backend=cb)
        with span("repro.refine.upload"):
            (ri32, si32), _ = pad_rows_pow2([np.asarray(cs.ri, np.int32),
                                             np.asarray(cs.si, np.int32)])
            ri_dev, si_dev = to_device(ri32), to_device(si32)
        with span("repro.refine.lanes"):
            res, unc, perm_p = RF.fused_refine_lanes(
                plan.R, plan.S, ri_dev, si_dev, perm, n_indec, predicate)
            N = len(cs)
            hit_ref = jnp.zeros(N, bool).at[perm_p].set(res, mode="drop")
            cs.hit = (cs.status == TRUE_HIT) | hit_ref
            cs.unc = jnp.zeros(N, bool).at[perm_p].set(unc, mode="drop")
        return cs

    return StagePlan([Stage("mbr", mbr_stage),
                      Stage("filter", filter_stage),
                      Stage("refine", refine_stage)])


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------

def execute_fused(plan, predicate: str, stats):
    """Run the fused chain; returns (result pairs [K,2] int64, stats).

    Result rows reproduce the staged ordering exactly: TRUE_HIT pairs in
    frame order, then refined-true INDECISIVE pairs in frame order.
    Each step runs in a span; ``JoinPlan.execute`` turns their host
    seconds into the times in ``stats``: the stage times (``t_mbr``/
    ``t_filter``/``t_refine``) cover host work and dispatch only, and the
    device work of the chain lands in ``t_sync`` — the end-of-chain gather
    (``repro.sync.gather``) plus the f64 host escalation of FMA-borderline
    pairs (``repro.sync.escalate``, the one permitted round trip).
    """
    from . import refine as RF
    cs = build_stage_plan(plan, predicate).run()
    if len(cs) == 0:
        return np.zeros((0, 2), np.int64), stats
    with span("repro.join.assemble"):
        frame = np.stack([np.asarray(cs.ri, np.int64),
                          np.asarray(cs.si, np.int64)], axis=1)
    lanes = (cs.status, cs.hit, cs.unc)
    if cs.valid is not None:
        lanes += (cs.valid,)
    with span("repro.sync.gather"):
        got = to_host(*lanes)
    status_h, hit_h, unc_h = got[0], np.array(got[1]), got[2]
    valid_h = got[3] if cs.valid is not None else np.ones(len(cs), bool)
    note_routed(RF._ESCALATED, np.count_nonzero(unc_h))
    with span("repro.sync.escalate"):
        if unc_h.any():
            # f64 escalation of the FMA-borderline pairs — identical to the
            # staged jnp refine backend's per-bucket escalation set
            esc = frame[unc_h]
            hit_h[unc_h] = RF.refine(plan.R, plan.S, esc,
                                     predicate=predicate, backend="numpy")

    with span("repro.join.assemble"):
        stats.n_candidates = int(valid_h.sum())
        stats.n_true_hits = int(np.sum((status_h == TRUE_HIT) & valid_h))
        stats.n_true_negs = int(np.sum((status_h == TRUE_NEG) & valid_h))
        stats.n_indecisive = int(np.sum((status_h == INDECISIVE) & valid_h))
        indec = status_h == INDECISIVE
        results = np.concatenate([frame[status_h == TRUE_HIT],
                                  frame[indec & hit_h]], axis=0)
        stats.n_results = len(results)
    return results, stats
