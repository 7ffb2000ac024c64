"""``chip_smoke.py`` at a tiny scale on the CPU: every phase function runs
with the Pallas kernels interpreted and checks its pair set against the
host reference; the script itself refuses to run without a TPU."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"

#: tiny workload scale: 60 x 200 polygons
TINY_K = 0.05


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load_smoke()


@pytest.fixture(scope="module")
def tiny(smoke):
    R, S, n_order = smoke.workload(TINY_K)
    ref, plan = smoke.host_reference(R, S, n_order, "april",
                                     ("intersects", "within"))
    return R, S, n_order, ref, (plan.approx_r, plan.approx_s)


def test_workload_scaling(smoke):
    R, S, n_order = smoke.workload(TINY_K)
    assert (len(R), len(S), n_order) == (60, 200, 6)
    # the spec's raster orders at the scales the chip runs
    assert [8 + round(__import__("math").log(k, 4)) for k in (10, 40)] \
        == [10, 11]


@pytest.mark.parametrize("phase", ["a", "b"])
def test_april_phases_match_reference(smoke, tiny, phase):
    R, S, n_order, ref, prebuilt = tiny
    lines = getattr(smoke, f"phase_{phase}")(R, S, n_order, ref, prebuilt)
    assert [ln.split(":")[0] for ln in lines] == [
        f"phase {phase} intersects", f"phase {phase} within"]
    assert all("host_rows=" in ln and "wall_s=" in ln for ln in lines)


def test_ri_phase_matches_reference(smoke, tiny):
    R, S, n_order, ref, _ = tiny
    (line,) = smoke.phase_c(R, S, n_order, ref)
    assert line.startswith("phase c intersects:")


def test_service_phase_matches_reference(smoke, tiny):
    R, S, n_order, ref, _ = tiny
    (line,) = smoke.phase_d(R, S, n_order, ref["intersects"], n_queries=8,
                            timeout_s=120.0)
    assert "queries=8" in line


def test_mesh_phase_on_one_device(smoke):
    from repro.datagen import make_dataset
    from repro.spatial import JoinPlan
    want = JoinPlan(make_dataset("T1", seed=0, count=60),
                    make_dataset("T2", seed=1, count=200),
                    n_order=8).execute("intersects")[0]
    lines = smoke.run_mesh(1, counts=(60, 200))
    assert len(lines) == 4
    assert all(f"results={len(want)} " in ln for ln in lines)


def test_check_equal_rejects_a_differing_pair_set(smoke):
    smoke.check_equal("same", [[0, 1], [2, 3]], [[2, 3], [0, 1]])
    with pytest.raises(AssertionError):
        smoke.check_equal("missing", [[0, 1]], [[0, 1], [2, 3]])
    with pytest.raises(AssertionError):
        smoke.check_equal("duplicate", [[0, 1], [0, 1]], [[0, 1]])


def _run(script: Path, cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_exits_nonzero_without_tpu():
    proc = _run(SCRIPT, ROOT)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_exits_nonzero_without_the_repository(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    proc = _run(lone, tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
