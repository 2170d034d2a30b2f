"""`chip_smoke.py` on the CPU: every phase at tiny sizes with the Pallas
kernels in interpret mode, so a wrong path, argument or check fails here
before it costs a chip run. The script itself refuses to run off a TPU."""
import os
import shutil
import subprocess
import sys

import pytest

from repro.hash import HashPlan, hasher as hasher_mod
from repro.parallel.sharding import data_mesh

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)
import chip_smoke as cs  # noqa: E402

TINY = cs.Sizes(
    engine_rows=16, engine_width=40, engine_hashes=2, serve_rows=3,
    serve_width=6, bloom_items=20_000, bloom_wave=48, bloom_waves=3,
    bloom_repeats=8, tree_bytes=1 << 16, smoke_arch=True, n_requests=12,
    max_new_tokens=3, admission_items=1 << 12)


@pytest.fixture
def interpret(monkeypatch, tmp_path):
    """Steer every default-planned Hasher onto the interpret backend (on a
    TPU the default is 'pallas') and keep checkpoints out of the checkout."""
    monkeypatch.setattr(hasher_mod, "default_plan",
                        lambda: HashPlan(backend="interpret"))
    monkeypatch.setattr(cs, "WORKDIR", str(tmp_path / "smoke"))
    return "interpret"


@pytest.mark.parametrize("phase", cs.ONE_CHIP, ids=lambda f: f.__name__)
def test_one_chip_phase_tiny(interpret, phase):
    phase(TINY, interpret, 3)


@pytest.mark.parametrize("phase", cs.MESH, ids=lambda f: f.__name__)
def test_mesh_phase_tiny(interpret, phase):
    phase(TINY, interpret, 3, data_mesh())


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_refuses_outside_a_checkout(tmp_path):
    """Alone in a directory (no `src/repro` beside it) the script stops
    before it imports JAX, whatever the platform."""
    script = shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, script], capture_output=True,
                         text=True, env=env, cwd=tmp_path, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "run from a checkout" in out.stderr
