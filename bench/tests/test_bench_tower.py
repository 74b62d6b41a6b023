"""The tower cell, ``risk-tower8-1chip``, on the CPU at a small size: a sound
run is correct in every layer, and a run whose timed path alters one
layer's answer, leaves a layer out, or is the bfloat16 control is not.
Also its terms, and the work count its roofline share reads."""
import json
import pathlib
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import counts, harness
from bench.drivers import risk_tower as driver
from bench.reference import risk as one_layer_reference
from bench.reference import risk_tower as reference

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "risk-tower8-1chip"
SMALL = dict(num_trials=256, events_per_trial=40, num_elts=3,
             event_catalog=512, chunk_events=16)


def small_cell():
    cell = harness.resolve(BENCHMARK, CELL)
    cell.config.update(SMALL)
    return cell


def run(cell, seed=2**31 + 11, seconds=0.2):
    return harness.run_cell(cell, seed, seconds, False, time.perf_counter(),
                            require_chip=False)


def test_the_cell_prices_eight_layers_on_one_chip():
    cell = harness.resolve(BENCHMARK, CELL)
    assert cell.chips == cell.config["devices"] == 1
    assert cell.config["num_layers"] == 8
    assert [m["name"] for m in cell.end_to_end] == ["trials_per_s",
                                                    "setup_s"]


def test_sound_run_is_correct_in_every_layer():
    out = run(small_cell())
    assert out["correct"] is True
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"trials_per_s", "setup_s"}
    c = out["checks"]
    assert c["ylt_rel_err"]["value"] < c["ylt_rel_err"]["limit"] == 3e-5
    assert c["analyses_incomplete"] == {"value": 0, "limit": 0}
    assert c["compiles_in_window"] == {"value": 0, "limit": 0}


@pytest.fixture
def one_layer_altered(monkeypatch):
    """Layer 3's year-loss of its largest trial is 1% off where the kernel
    produces it."""
    from repro.kernels import ops
    real = ops.aggregate_loss

    def altered(*a, **k):
        out = real(*a, **k)
        return out.at[jnp.argmax(out[:, 3]), 3].multiply(1.01)

    monkeypatch.setattr(ops, "aggregate_loss", altered)


@pytest.fixture
def layer_left_out(monkeypatch):
    """The step prices every layer but the top one, whose column stays
    zero."""
    from repro.kernels import ops
    real = ops.aggregate_loss

    def without_top(ids, elt, ret, lim, agg_ret, agg_lim, **k):
        out = real(ids, elt, ret[:-1], lim[:-1], agg_ret[:-1], agg_lim[:-1],
                   **k)
        return jnp.pad(out, ((0, 0), (0, 1)))

    monkeypatch.setattr(ops, "aggregate_loss", without_top)


@pytest.fixture
def bfloat16_control(monkeypatch):
    """The control: the reference in bfloat16 in the step's place."""
    from repro.kernels import ops

    def control(ids, elt, ret, lim, agg_ret, agg_lim, chunk=128,
                variant=None):
        bf = jnp.bfloat16
        cols = []
        for l in range(ret.shape[0]):
            loss = one_layer_reference._trial_losses(ids, elt, ret[l],
                                                     lim[l], bf).astype(bf)
            cols.append(jnp.minimum(
                jnp.maximum(loss - jnp.asarray(agg_ret[l], bf), 0),
                jnp.asarray(agg_lim[l], bf)).astype(jnp.float32))
        return jnp.stack(cols, axis=1)

    monkeypatch.setattr(ops, "aggregate_loss", control)


@pytest.mark.parametrize("fault", ["one_layer_altered", "layer_left_out",
                                   "bfloat16_control"])
def test_broken_timed_path_is_not_correct(fault, request):
    request.getfixturevalue(fault)
    out = run(small_cell())
    assert out["correct"] is False
    c = out["checks"]["ylt_rel_err"]
    assert c["value"] > c["limit"]


def test_tower_terms_stack_the_layers():
    """Each layer attaches where the one below exhausts, the first at the
    one-layer retention; the top exhausts near the 99.9th percentile; the
    aggregate terms are 0.1 and 2 x each layer's expected annual loss."""
    conf = json.loads((ROOT / "bench" / "configs" /
                       "risk-tower8.json").read_text())
    t = conf["tables"]
    mu, sigma = t["loss_lognormal_mu"], t["loss_lognormal_sigma"]
    q25 = np.exp(mu + sigma * -0.6744897501960817)
    jitter = np.linspace(0.5, 1.5, conf["num_elts"]).astype(np.float32)
    (ret, lim), (agg_ret, agg_lim) = driver.tower_terms(conf, q25 * jitter)
    assert ret.shape == lim.shape == (8, conf["num_elts"])
    np.testing.assert_allclose(ret[0], q25 * jitter, rtol=1e-6)
    np.testing.assert_allclose(ret[1:], ret[:-1] + lim[:-1], rtol=1e-6)
    top = (ret[-1] + lim[-1]) / jitter
    z = (np.log(top) - mu) / sigma
    assert np.allclose(z, 3.0228, atol=1e-3)        # the 99.87th percentile
    np.testing.assert_allclose(agg_lim, 20 * agg_ret, rtol=1e-6)
    assert (agg_ret > 0).all()


def test_expected_excess_by_sampling():
    rng = np.random.default_rng(0)
    x = np.exp(10.0 + 1.5 * rng.standard_normal(2_000_000))
    for k in (5e3, 1e5, 1e6):
        assert driver.expected_excess(10.0, 1.5, np.float64(k)) == \
            pytest.approx(np.maximum(x - k, 0).mean(), rel=0.03)


def test_reference_is_the_one_layer_reference_per_layer():
    rng = np.random.default_rng(1)
    yet = rng.integers(0, 9, (5, 7)).astype(np.int32)
    elt = rng.uniform(0, 10, (9, 2)).astype(np.float32)
    elt[0] = 0
    ret = rng.uniform(0, 3, (3, 2)).astype(np.float32)
    lim = rng.uniform(1, 6, (3, 2)).astype(np.float32)
    loss = reference.trial_losses(yet, elt, ret, lim)
    assert loss.shape == (5, 3)
    for l in range(3):
        np.testing.assert_array_equal(
            loss[:, l],
            one_layer_reference.trial_losses(yet, elt, ret[l], lim[l]))
    agg = np.array([1.0, 2.0, 3.0], np.float32)
    ylt = reference.year_losses(loss, agg, 2 * agg)
    np.testing.assert_array_equal(
        ylt[:, 1], one_layer_reference.year_losses(loss[:, 1], 2.0, 4.0))


def test_step_work_count_is_within_a_percent_of_the_layered_count():
    """``risk_step_work`` counts one layer; at the tower's widths its least
    time is at most 1% under that of an exact count of 8 layers (each
    event and ELT clipped per layer, one YLT column a layer) and never
    over it: the roofline share the cell reads is the layered one or a
    little under."""
    T, K, M, L, cat = 65536, 1000, 15, 8, 2_000_000
    exact_flops = 4.0 * T * K * M * L
    exact_bytes = 4.0 * (T * K + (cat + 1) * M + T * L)
    peak = counts.peaks("TPU v5 lite")
    exact, bound = counts.least_time_s(exact_flops, exact_bytes, peak)
    counted, _ = counts.least_time_s(*counts.risk_step_work(T, K, M, cat),
                                     peak)
    assert bound == "memory"
    assert 0.99 * exact <= counted <= exact
