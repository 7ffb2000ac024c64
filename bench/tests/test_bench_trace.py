"""The trace reduction on small hand-made event lists."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import spec, trace  # noqa: E402

STAGES = spec.load_json(spec.BENCH / "stages.json")["stages"]


def test_union_merges_overlaps_and_gaps():
    iv = [(0, 10), (5, 15), (20, 30), (25, 26), (40, 41)]
    assert trace.union_ns(iv) == 15 + 10 + 1
    assert trace.union_ns([]) == 0


def test_clip_keeps_the_inside():
    assert trace.clip([(0, 10), (5, 15), (20, 30)], 8, 22) == [
        (8, 10), (8, 15), (20, 22)]


def test_idle_share_of_a_window():
    ops = [("a", 0, 40, "p"), ("b", 30, 50, "p"), ("c", 80, 90, "p")]
    busy = trace.union_ns([(s, e) for _, s, e, _ in ops])
    assert busy == 60
    assert 1 - busy / 100 == pytest.approx(0.4)


@pytest.mark.parametrize("program,stage", [
    ("jit__fused_tri_bucket_jnp", "filter"),
    ("jit_run", "refine"),
    ("jit__compact_impl", "refine"),
    ("jit_mask", "mbr"),
    ("jit_something_else", "other"),
])
def test_stage_attribution_through_stages_json(program, stage):
    assert trace.stage_of(program, STAGES) == stage


def test_stage_time_is_a_union_per_stage():
    ops = [("f1", 0, 10, "jit__fused_tri_bucket_jnp"),
           ("f2", 5, 12, "jit__fused_tri_bucket_jnp"),
           ("r", 20, 30, "jit_run"),
           ("x", 30, 31, "jit_other")]
    assert trace.stage_ns(ops, STAGES) == {"filter": 12, "refine": 10,
                                           "other": 1}


def test_top_ops_ranks_by_total_time():
    ops = [("%a = s32[8] fusion(s32[8] %x)", 0, 5, "jit_f(123)"),
           ("%b = s32[8] copy(%y)", 5, 20, "jit_f(123)"),
           ("%a = s32[8] fusion(s32[8] %x)", 20, 32, "jit_f(123)")]
    assert trace.top_ops(ops, 1) == [["jit_f/a", 17e-9]]


def test_idle_gaps_are_named_by_the_innermost_span():
    ops = [("a", 10, 20, "p"), ("b", 60, 70, "p")]
    spans = [("bench.window", 0, 100), ("bench.join", 1, 99),
             ("repro.join", 2, 98), ("repro.mbr", 22, 58),
             ("repro.mbr.frame", 25, 55)]
    gaps = trace.idle_gaps(ops, spans, 0, 100, 10)
    assert gaps[0] == ["repro.mbr.frame", 40e-9]
    assert [g[1] for g in gaps] == [40e-9, 30e-9, 10e-9]
    assert gaps[1][0] == "repro.join"


def test_load_keeps_the_benchmarks_and_the_programs_spans(tmp_path):
    """A real profiler trace on the CPU: ``bench.*`` spans and the
    program's own ``repro.*`` spans (opened inside a trace block, as
    ``JoinPlan.execute`` opens one) are kept, other host events are not."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro.runtime.trace import span, trace_block
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("bench.window"), trace_block():
            with span("repro.mbr.frame"):
                jax.numpy.arange(8).sum().block_until_ready()
            with TraceAnnotation("other.step"):
                pass
    finally:
        jax.profiler.stop_trace()
    _, spans = trace.load(str(tmp_path))
    names = {name for name, _, _ in spans}
    assert {"bench.window", "repro.mbr.frame"} <= names
    assert "other.step" not in names
    assert all(n.startswith(trace.SPAN_PREFIXES) for n in names)
    assert all(s <= e for _, s, e in spans)
