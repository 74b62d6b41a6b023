"""The plain Algorithm 3 reference: by hand, and against the program at the
repository's reduced sizes."""
import importlib.util
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import risk as reference

DRIVER = pathlib.Path(__file__).resolve().parents[1] / "drivers" / "risk.py"


def test_by_hand():
    # 2 ELTs, catalog of 3 events (row 0 is the pad event)
    elt = np.array([[0, 0], [10, 1], [30, 5], [4, 50]], np.float32)
    ret = np.array([5, 2], np.float32)
    lim = np.array([20, 40], np.float32)
    yet = np.array([[1, 2, 0], [3, 3, 1]], np.int32)
    # trial 0: event 1 -> min(max(10-5,0),20)=5, min(max(1-2,0),40)=0
    #          event 2 -> 20, 3; pad -> 0          total 28
    # trial 1: event 3 -> 0, 40 (twice) ; event 1 -> 5, 0   total 85
    loss = reference.trial_losses(yet, elt, ret, lim)
    np.testing.assert_array_equal(loss, [28, 85])
    np.testing.assert_array_equal(reference.year_losses(loss, 30, 50),
                                  [0, 50])
    np.testing.assert_array_equal(reference.year_losses(loss, 10, 70),
                                  [18, 70])


def test_bfloat16_rounds():
    yet = np.full((1, 300), 1, np.int32)
    elt = np.array([[0.0], [1.001]], np.float32)
    big = np.array([1e9], np.float32)
    f32 = reference.trial_losses(yet, elt, np.zeros(1, np.float32), big)
    bf16 = reference.trial_losses(yet, elt, np.zeros(1, np.float32), big,
                                  dtype=jnp.bfloat16)
    assert f32[0] == pytest.approx(300.3, rel=1e-6)
    # 1.001 rounds to 1.0 in bfloat16: 300.0 against 300.3
    assert bf16[0] == 300.0


def test_matches_the_program_at_reduced_sizes():
    from repro.configs.risk_app import CONFIG
    from repro.risk.analysis import AggregateRiskAnalysis
    from repro.risk.tables import generate
    spec = importlib.util.spec_from_file_location("bench_driver_risk", DRIVER)
    driver = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(driver)
    cfg = CONFIG.reduced()
    for seed in (0, 1):
        tables = generate(cfg, seed)
        ylt = AggregateRiskAnalysis(cfg).run_tenant_chunked(tables).ylt
        loss = reference.trial_losses(tables.yet, tables.elt_losses,
                                      tables.occ_ret, tables.occ_lim)
        want = reference.year_losses(loss, tables.agg_ret, tables.agg_lim)
        err = np.abs(ylt - want) / np.maximum(loss, 1.0)
        assert err.max() < driver.YLT_REL_ERR_LIMIT
        assert (want > 0).any()
