"""The chip smoke's phases at a CPU size, and its refusal to run off
the chip.  ``chip_smoke.py`` itself runs only on a TPU; these tests call
its phase functions directly at about scale 10."""
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def test_batch_and_serving_phases():
    state = chip_smoke.batch_phase(10, seed=0)
    chip_smoke.serving_phase(state, seed=0)


def test_pallas_phase():
    chip_smoke.pallas_phase(9, seed=0, iters=3)


def test_sharded_phase_on_one_device():
    chip_smoke.sharded_phase(9, seed=0, shards=1)


def test_reference_matches_dense_oracle():
    import numpy as np
    from repro.core.pagerank import pagerank_reference
    from repro.graphs.generators import rmat
    g = rmat(7, 8, seed=3)
    ref = chip_smoke.reference_pagerank(chip_smoke.transition(g),
                                        np.ones(g.num_nodes), 20)
    want = pagerank_reference(g, num_iterations=20,
                              dangling="redistribute")
    np.testing.assert_allclose(ref, want, rtol=1e-12)


def _run(script, cwd):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_refuses_off_the_chip(where, tmp_path):
    """No TPU, or no repo beside the script: non-zero exit, no result."""
    script = os.path.join(ROOT, "chip_smoke.py")
    if where == "alone":
        script = shutil.copy(script, tmp_path)
    proc = _run(script, tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
