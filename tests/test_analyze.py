"""tools/analyze: fixture-verified true positives AND true negatives for
every rule, the suppression syntax, baseline round-trips, and the repo
gate itself (current tree must be analyze-clean with a minimal baseline).

Fixtures are written under ``tmp_path`` mirroring the repo layout (the
passes scope by repo-relative path), parsed with :class:`SourceFile`
rooted at ``tmp_path``, and run through one pass at a time.
"""
import textwrap

import pytest

from tools.analyze import (ALL_PASSES, all_rules, collect_files,
                           diff_baseline, load_baseline, run_passes,
                           save_baseline)
from tools.analyze.backend_parity import BackendParityPass
from tools.analyze.core import ROOT, Finding, SourceFile
from tools.analyze.deprecation import DeprecationPass
from tools.analyze.host_sync import HostSyncPass
from tools.analyze.lock_discipline import LockDisciplinePass
from tools.analyze.pallas_constraint import PallasConstraintPass
from tools.analyze.precision import PrecisionPass


def _run(tmp_path, rel, code, pass_):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(code))
    src = SourceFile(p, root=tmp_path)
    return run_passes([pass_], [src], root=tmp_path)


def _rules(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# host-sync (HS001/HS002)
# ---------------------------------------------------------------------------

HS001_TP = """
    import jax.numpy as jnp

    def stage(a):
        x = jnp.sum(a)
        return float(x)
"""

HS001_TN = """
    import numpy as np

    def stage(a):
        y = np.sum(a)
        return float(y)
"""


def test_hs001_true_positive(tmp_path):
    out = _run(tmp_path, "src/repro/spatial/mod.py", HS001_TP,
               HostSyncPass())
    assert _rules(out) == ["HS001"]


def test_hs001_true_negative(tmp_path):
    out = _run(tmp_path, "src/repro/spatial/mod.py", HS001_TN,
               HostSyncPass())
    assert out == []


def test_hs001_out_of_scope_path_ignored(tmp_path):
    out = _run(tmp_path, "src/repro/datagen/mod.py", HS001_TP,
               HostSyncPass())
    assert out == []


HS002_TP = """
    import time

    def bench(fn, x):
        t0 = time.perf_counter()
        out = fn(x)
        dt = time.perf_counter() - t0
        return out, dt
"""

HS002_TN = """
    import time
    import jax

    def bench(fn, x):
        t0 = time.perf_counter()
        out = fn(x)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        return out, dt
"""


def test_hs002_true_positive(tmp_path):
    out = _run(tmp_path, "benchmarks/bench_mod.py", HS002_TP,
               HostSyncPass())
    assert _rules(out) == ["HS002"]


def test_hs002_true_negative(tmp_path):
    out = _run(tmp_path, "benchmarks/bench_mod.py", HS002_TN,
               HostSyncPass())
    assert out == []


def test_hs002_pairs_read_with_closest_preceding_start(tmp_path):
    # two regions reusing t0: the synced first region must stay clean and
    # only the unsynced second region is flagged (regression: "latest
    # start wins" misattributed the region bounds)
    code = """
        import time
        import jax

        def bench(fn, x):
            t0 = time.perf_counter()
            a = fn(x)
            jax.block_until_ready(a)
            d1 = time.perf_counter() - t0
            t0 = time.perf_counter()
            b = fn(x)
            d2 = time.perf_counter() - t0
            return d1, d2
    """
    out = _run(tmp_path, "benchmarks/bench_mod.py", code, HostSyncPass())
    assert _rules(out) == ["HS002"]
    assert "d2" in (tmp_path / "benchmarks/bench_mod.py").read_text() \
        .splitlines()[out[0].line - 1]


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------

def test_suppression_same_line(tmp_path):
    code = """
        import jax.numpy as jnp

        def stage(a):
            x = jnp.sum(a)
            return float(x)  # analyze: ignore[HS001] stage-boundary sync
    """
    assert _run(tmp_path, "src/repro/spatial/mod.py", code,
                HostSyncPass()) == []


def test_suppression_standalone_comment_above(tmp_path):
    code = """
        import jax.numpy as jnp

        def stage(a):
            x = jnp.sum(a)
            # analyze: ignore[HS001] intended host hand-off
            return float(x)
    """
    assert _run(tmp_path, "src/repro/spatial/mod.py", code,
                HostSyncPass()) == []


def test_suppression_other_rule_does_not_silence(tmp_path):
    code = """
        import jax.numpy as jnp

        def stage(a):
            x = jnp.sum(a)
            return float(x)  # analyze: ignore[HS002]
    """
    assert _rules(_run(tmp_path, "src/repro/spatial/mod.py", code,
                       HostSyncPass())) == ["HS001"]


def test_suppression_bare_ignore_silences_all(tmp_path):
    code = """
        import jax.numpy as jnp

        def stage(a):
            x = jnp.sum(a)
            return float(x)  # analyze: ignore
    """
    assert _run(tmp_path, "src/repro/spatial/mod.py", code,
                HostSyncPass()) == []


# ---------------------------------------------------------------------------
# precision (FP001/FP002)
# ---------------------------------------------------------------------------

FP001_TP = """
    import jax.numpy as jnp

    def classify(ax, ay, bx, by):
        d = ax * by - ay * bx
        return jnp.where(d > 0, 1, -1)
"""

FP001_TN = """
    import jax.numpy as jnp

    _EPS_GUARD = 2.0 ** -44

    def classify(ax, ay, bx, by):
        d = ax * by - ay * bx
        sure = jnp.abs(d) > _EPS_GUARD
        return jnp.where(d > 0, 1, -1), sure
"""


def test_fp001_true_positive(tmp_path):
    out = _run(tmp_path, "src/repro/core/geo.py", FP001_TP,
               PrecisionPass())
    assert _rules(out) == ["FP001"]


def test_fp001_true_negative_guard_band(tmp_path):
    out = _run(tmp_path, "src/repro/core/geo.py", FP001_TN,
               PrecisionPass())
    assert out == []


def test_fp002_true_positive(tmp_path):
    code = """
        import jax

        def setup():
            jax.config.update("jax_enable_x64", True)
    """
    out = _run(tmp_path, "src/repro/core/setup.py", code, PrecisionPass())
    assert _rules(out) == ["FP002"]


def test_fp002_true_negative_scoped_context(tmp_path):
    code = """
        import jax
        import numpy as np

        def compute(x):
            with jax.enable_x64(True):
                return np.asarray(x)
    """
    assert _run(tmp_path, "src/repro/core/setup.py", code,
                PrecisionPass()) == []


# ---------------------------------------------------------------------------
# lock-discipline (LD001/LD002)
# ---------------------------------------------------------------------------

LD001_TP = """
    import threading

    class Svc:
        def __init__(self):
            self._lock = threading.Lock()
            self.items = []

        def start(self):
            def loop():
                self.drain()
            threading.Thread(target=loop, daemon=True).start()

        def drain(self):
            self.items.append(1)
"""

LD001_TN = """
    import threading

    class Svc:
        def __init__(self):
            self._lock = threading.Lock()
            self.items = []

        def start(self):
            def loop():
                self.drain()
            threading.Thread(target=loop, daemon=True).start()

        def drain(self):
            with self._lock:
                self.items.append(1)
"""


def test_ld001_true_positive(tmp_path):
    out = _run(tmp_path, "src/repro/spatial/svc.py", LD001_TP,
               LockDisciplinePass())
    assert "LD001" in _rules(out)


def test_ld001_true_negative(tmp_path):
    assert _run(tmp_path, "src/repro/spatial/svc.py", LD001_TN,
                LockDisciplinePass()) == []


def test_ld001_method_call_is_not_a_field(tmp_path):
    # `self._handle(k).append(...)` mutates the returned object, not a
    # field named `_handle` (regression: methods misclassified as fields)
    code = """
        import threading

        class Svc:
            def __init__(self):
                self._lock = threading.Lock()

            def _handle(self, k):
                return []

            def start(self):
                def loop():
                    self.work()
                threading.Thread(target=loop, daemon=True).start()

            def work(self):
                self._handle(1).append(2)
    """
    assert _run(tmp_path, "src/repro/spatial/svc.py", code,
                LockDisciplinePass()) == []


def test_ld001_thread_safe_fields_exempt(tmp_path):
    code = """
        import threading

        class Svc:
            def __init__(self):
                self._lock = threading.Lock()
                self._stop = threading.Event()

            def start(self):
                def loop():
                    self.work()
                threading.Thread(target=loop, daemon=True).start()

            def work(self):
                self._stop.set()

            def stop(self):
                self._stop.set()
    """
    assert _run(tmp_path, "src/repro/spatial/svc.py", code,
                LockDisciplinePass()) == []


LD002_TP = """
    import threading

    class Two:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()
            self.x = 0

        def m1(self):
            with self._a:
                with self._b:
                    self.x = 1

        def m2(self):
            with self._b:
                with self._a:
                    self.x = 2
"""

LD002_TN = """
    import threading

    class Two:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()
            self.x = 0

        def m1(self):
            with self._a:
                with self._b:
                    self.x = 1

        def m2(self):
            with self._a:
                with self._b:
                    self.x = 2
"""


def test_ld002_true_positive(tmp_path):
    out = _run(tmp_path, "src/repro/spatial/two.py", LD002_TP,
               LockDisciplinePass())
    assert "LD002" in _rules(out)


def test_ld002_true_negative_consistent_order(tmp_path):
    out = _run(tmp_path, "src/repro/spatial/two.py", LD002_TN,
               LockDisciplinePass())
    assert "LD002" not in _rules(out)


# ---------------------------------------------------------------------------
# pallas-constraint (PL001/PL002/PL003)
# ---------------------------------------------------------------------------

def test_pl001_true_positive_default_and_call(tmp_path):
    code = """
        from jax.experimental import pallas as pl

        def launch(x, block_m: int = 100):
            return run(x, block_n=96)
    """
    out = _run(tmp_path, "src/repro/kernels/k.py", code,
               PallasConstraintPass())
    assert _rules(out) == ["PL001", "PL001"]


def test_pl001_true_negative_pow2(tmp_path):
    code = """
        from jax.experimental import pallas as pl

        def launch(x, block_m: int = 128):
            return run(x, block_n=64)
    """
    assert _run(tmp_path, "src/repro/kernels/k.py", code,
                PallasConstraintPass()) == []


def test_pl002_true_positive(tmp_path):
    code = """
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            v = x_ref[0]
            if v > 0:
                o_ref[0] = v
    """
    out = _run(tmp_path, "src/repro/kernels/k.py", code,
               PallasConstraintPass())
    assert _rules(out) == ["PL002"]


def test_pl002_true_negative_pl_when(tmp_path):
    code = """
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            v = x_ref[0]
            o_ref[0] = jnp.where(v > 0, v, 0.0)
    """
    assert _run(tmp_path, "src/repro/kernels/k.py", code,
                PallasConstraintPass()) == []


def test_pl003_true_positive_captured_host_state(tmp_path):
    code = """
        from jax.experimental import pallas as pl

        state = dict(scale=2.0)

        def kernel(x_ref, o_ref):
            o_ref[0] = x_ref[0] * state["scale"]
    """
    out = _run(tmp_path, "src/repro/kernels/k.py", code,
               PallasConstraintPass())
    assert _rules(out) == ["PL003"]


def test_pl003_true_negative_module_constant(tmp_path):
    code = """
        from jax.experimental import pallas as pl

        SCALE = 2.0
        NEG, HIT, MAYBE = 0, 1, 2

        def kernel(x_ref, o_ref):
            o_ref[0] = x_ref[0] * SCALE + MAYBE
    """
    assert _run(tmp_path, "src/repro/kernels/k.py", code,
                PallasConstraintPass()) == []


# ---------------------------------------------------------------------------
# deprecation (DP001)
# ---------------------------------------------------------------------------

def test_dp001_true_positive(tmp_path):
    code = """
        from repro.spatial import JoinPlan

        def make(R, S):
            return JoinPlan(R, S, backend="jnp")
    """
    out = _run(tmp_path, "src/repro/spatial/user.py", code,
               DeprecationPass())
    assert _rules(out) == ["DP001"]


def test_dp001_true_negative(tmp_path):
    code = """
        from repro.spatial import JoinPlan

        def make(R, S):
            return JoinPlan(R, S, filter_backend="jnp")
    """
    assert _run(tmp_path, "src/repro/spatial/user.py", code,
                DeprecationPass()) == []


# ---------------------------------------------------------------------------
# backend-parity (BE001/BE002/BE003)
# ---------------------------------------------------------------------------

def test_be001_true_positive_incomplete_filter():
    from repro.spatial.filters import register_filter, unregister_filter
    from repro.spatial.filters.base import IntermediateFilter

    class StubFilter(IntermediateFilter):
        # overrides only the abstract pair; no sequential oracle, no
        # incremental-maintenance hooks -> protocol incomplete
        def build(self, *a, **kw):
            raise NotImplementedError

        def verdicts(self, *a, **kw):
            raise NotImplementedError

    register_filter("zz-stub", StubFilter)
    try:
        out = BackendParityPass()._be001(ROOT)
    finally:
        unregister_filter("zz-stub")
    stub = [f for f in out if f.snippet == "filter:zz-stub"]
    assert len(stub) == 1 and stub[0].rule == "BE001"
    assert "_verdict_one" in stub[0].message
    assert "patch_insert/patch_delete" in stub[0].message


def test_be001_true_negative_builtin_registry():
    assert BackendParityPass()._be001(ROOT) == []


def _fake_repo(tmp_path, *, readme, design, pipeline, flags):
    (tmp_path / "README.md").write_text(" ".join(readme))
    (tmp_path / "DESIGN.md").write_text(" ".join(design))
    pp = tmp_path / "src/repro/spatial/pipeline.py"
    pp.parent.mkdir(parents=True, exist_ok=True)
    pp.write_text("# " + " ".join(pipeline) + "\n")
    lp = tmp_path / "src/repro/launch"
    lp.mkdir(parents=True, exist_ok=True)
    body = "\n".join(
        f'ap.add_argument("--{k.replace("_", "-")}")' for k in flags)
    (lp / "spatial_join.py").write_text(body + "\n")
    (lp / "serve_join.py").write_text("\n")
    return tmp_path


ALL_KNOBS = ("filter_backend", "refine_backend", "mbr_backend",
             "build_backend", "pipeline_mode", "plan_mode",
             "tile_budget", "resume")


def test_be002_003_true_negative_fully_threaded(tmp_path):
    root = _fake_repo(tmp_path, readme=ALL_KNOBS, design=ALL_KNOBS,
                      pipeline=ALL_KNOBS, flags=ALL_KNOBS)
    assert BackendParityPass()._be002_003(root) == []


def test_be002_true_positive_undocumented_knob(tmp_path):
    readme = tuple(k for k in ALL_KNOBS if k != "mbr_backend")
    root = _fake_repo(tmp_path, readme=readme, design=ALL_KNOBS,
                      pipeline=ALL_KNOBS, flags=ALL_KNOBS)
    out = BackendParityPass()._be002_003(root)
    assert [(f.rule, f.path, f.snippet) for f in out] == \
        [("BE002", "README.md", "knob:mbr_backend")]


def test_be003_true_positive_missing_flag_and_pipeline(tmp_path):
    pipeline = tuple(k for k in ALL_KNOBS if k != "refine_backend")
    flags = tuple(k for k in ALL_KNOBS if k != "build_backend")
    root = _fake_repo(tmp_path, readme=ALL_KNOBS, design=ALL_KNOBS,
                      pipeline=pipeline, flags=flags)
    out = BackendParityPass()._be002_003(root)
    assert sorted((f.rule, f.snippet) for f in out) == [
        ("BE003", "knob:build_backend"), ("BE003", "knob:refine_backend")]


def test_deprecated_backend_alias_is_not_a_parity_knob():
    from tools.analyze.backend_parity import collect_knobs
    knobs = collect_knobs(ROOT)
    assert "backend" not in knobs
    assert set(ALL_KNOBS) <= set(knobs)


# ---------------------------------------------------------------------------
# baseline mechanics and the repo gate
# ---------------------------------------------------------------------------

def _f(rule, path, snippet):
    return Finding(rule=rule, path=path, line=1, message="m",
                   snippet=snippet)


def test_baseline_round_trip(tmp_path):
    p = tmp_path / "baseline.json"
    found = [_f("HS001", "a.py", "x = 1"), _f("HS001", "a.py", "x = 1"),
             _f("LD001", "b.py", "y = 2")]
    save_baseline(found, p)
    diff = diff_baseline(found, load_baseline(p))
    assert diff.clean


def test_baseline_is_line_number_independent(tmp_path):
    p = tmp_path / "baseline.json"
    save_baseline([_f("HS001", "a.py", "x = 1")], p)
    moved = [Finding(rule="HS001", path="a.py", line=99, message="m",
                     snippet="x = 1")]
    assert diff_baseline(moved, load_baseline(p)).clean


def test_baseline_flags_new_and_stale(tmp_path):
    p = tmp_path / "baseline.json"
    save_baseline([_f("HS001", "a.py", "x = 1"),
                   _f("LD001", "b.py", "y = 2")], p)
    current = [_f("HS001", "a.py", "x = 1"),
               _f("FP001", "c.py", "z = 3")]
    diff = diff_baseline(current, load_baseline(p))
    assert [f.key for f in diff.new] == [("FP001", "c.py", "z = 3")]
    assert diff.stale == [("LD001", "b.py", "y = 2", 1)]


def test_repo_is_analyze_clean_with_minimal_baseline():
    """The committed tree passes the gate AND the committed baseline has
    no stale (already-fixed) entries — it can only shrink."""
    files = collect_files(["src", "tools", "benchmarks"])
    findings = run_passes(ALL_PASSES, files)
    diff = diff_baseline(findings, load_baseline())
    assert not diff.new, "\n" + "\n".join(f.render() for f in diff.new)
    assert not diff.stale, diff.stale


def test_rule_catalog_is_complete_and_unique():
    rules = all_rules()
    assert set(rules) == {"HS001", "HS002", "FP001", "FP002", "LD001",
                          "LD002", "BE001", "BE002", "BE003", "PL001",
                          "PL002", "PL003", "DP001"}
    assert len(ALL_PASSES) == 6
