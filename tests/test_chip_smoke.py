"""chip_smoke.py: refuses to report without a TPU, and its one-chip phase
passes end to end at a tiny size on the CPU (the rehearsal of the chip
run)."""
import importlib.util
import json
import os

import numpy as np
import pytest

from repro.core import mesh2d
from repro.obs import get_tracer

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_without_tpu(capsys):
    rc = _smoke().main([])
    out = capsys.readouterr().out
    assert rc != 0
    assert '"ok"' not in out
    for line in out.splitlines():
        assert not line.startswith("{")


def test_host_relres_matches_graph_matvec():
    smoke = _smoke()
    g = mesh2d(9, 9, seed=3)
    lap = smoke.host_laplacian(g)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((g.n, 2))
    np.testing.assert_allclose(lap @ x, g.laplacian_matvec(x), atol=1e-9)
    b = smoke.random_rhs(rng, g.n, 2)
    assert np.allclose(b.mean(axis=0), 0.0, atol=1e-6)
    assert np.all(smoke.host_relres(lap, b, np.zeros_like(x)) == 1.0)


@pytest.fixture
def process_tracer():
    """The phase turns the process-wide tracer on (it reads stage times
    from its spans); put it back as it was for the tests that follow."""
    tracer = get_tracer()
    was_enabled = tracer.enabled
    yield
    tracer.enabled = was_enabled
    tracer.clear()


def test_one_chip_phase_passes_on_cpu(capsys, process_tracer):
    assert _smoke().one_chip(24, 0) == []
    out = capsys.readouterr().out
    assert "matvec_impl=ref" in out
    assert out.count("host f64 relres") == 4
    # the phase itself never prints the result line; main() does
    assert not any(line.startswith("{") and json.loads(line).get("ok")
                   for line in out.splitlines())
