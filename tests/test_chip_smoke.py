"""chip_smoke.py's phases, rehearsed on the CPU at a tiny size.

The script itself refuses to run without a TPU; these tests call its
phase functions directly (Pallas kernels then run in interpret mode), so a
broken phase is caught before it costs chip time. The mesh phase needs
four devices and runs in a subprocess with forced host devices."""

import importlib.util
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, SCRIPT, "--docs", "100"], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 2
    assert r.stdout == ""                  # no result line, nothing built
    assert "no TPU" in r.stderr


def test_sparse_phase(smoke):
    assert smoke.run_sparse(1500, 3, 0, smoke.CompileLog()) == []


def test_dense_and_hybrid_phase(smoke):
    assert smoke.run_dense(1200, 2, 7) == []


def test_dense_kernel_check_rejects_the_interpreter(smoke):
    """On the CPU the served dot_topk is interpreted, and the check says so."""
    assert smoke.dense_kernel_compiled(300) != []


def test_mesh_phase_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = textwrap.dedent(f"""
        import importlib.util
        spec = importlib.util.spec_from_file_location("chip_smoke", {SCRIPT!r})
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.run_mesh(1500, 6, 0) == []
    """)
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"


@pytest.mark.parametrize("got,ok", [
    (["d0", "d2", "d1"], True),            # the reference order
    (["d0", "d1", "d2"], True),            # near-tie swap (d1, d2 1e-7 apart)
    (["d2", "d0", "d1"], False),           # a real rank swap
    (["d0", "d2"], False),                 # a missing hit
])
def test_parity_rule(smoke, got, ok):
    scores = {0: 3.0, 1: 2.0, 2: 2.0 * (1 + 1e-7)}
    ext_ids = ["doc0", "doc1", "doc2"]
    got = [f"doc{e[1:]}" for e in got]
    got_scores = [scores[int(e[3:])] for e in got]
    errs = smoke.compare("t", "q", got, got_scores, scores, ext_ids)
    assert (errs == []) is ok
