"""chip_smoke.py's phases at `.reduced()` sizes on the CPU, Pallas kernels in
interpret mode, and its refusal to run anywhere but on a TPU."""
import importlib.util
import json
import pathlib

import jax
import pytest

from repro.kernels import ops as kops

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def pallas_on():
    prev = kops._STATE["pallas"]
    kops.use_pallas(True)
    yield
    kops.use_pallas(prev)


def test_serving_phase_reduced(smoke):
    out = smoke.serving_phase(reduced=True, backend="pallas", n_requests=4,
                              prompt_lens=(16, 40, 64), new_tokens=6)
    assert out["tpu_custom_call"] is False        # interpret mode on the CPU
    assert out["greedy_agreement"] == 1.0
    assert out["max_abs_logit_diff"] < 1e-4


def test_mesh_serving_phase_reduced(smoke):
    # one CPU device: a 1x1 mesh still goes through the sharded stack
    out = smoke.mesh_serving_phase(mesh="1x1", layers=None, reduced=True,
                                   backend="pallas", n_requests=3,
                                   prompt_lens=(16, 32), new_tokens=4)
    assert out["token_exact"]


def test_risk_phase_reduced(smoke, pallas_on):
    errs = smoke.risk_phase(reduced=True, trials=None)
    assert set(errs) == {"sequential", "concurrent"}
    assert max(errs.values()) <= smoke.YLT_RTOL


def test_risk_multi_device_phase_reduced(smoke, pallas_on):
    assert smoke.risk_multi_device_phase(reduced=True,
                                         trials=None)["ylt_equal"]


def test_main_refuses_without_tpu(smoke, capsys):
    assert jax.default_backend() != "tpu"
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert not any(line.startswith("{") and json.loads(line).get("ok")
                   for line in out.splitlines())
