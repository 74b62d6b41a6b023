"""Risk application: engine numerics, tenancy equivalence, metrics."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.risk_app import RiskAppConfig
from repro.core.tenancy import TenancyConfig
from repro.kernels.ref import aggregate_loss_ref
from repro.risk import metrics
from repro.risk.analysis import AggregateRiskAnalysis
from repro.risk.tables import generate, paper_scale_nbytes


@pytest.fixture(scope="module")
def cfg():
    return RiskAppConfig().reduced()


@pytest.fixture(scope="module")
def tables(cfg):
    return generate(cfg, seed=0)


def _ref_ylt(tables):
    return np.asarray(aggregate_loss_ref(
        jnp.asarray(tables.yet), jnp.asarray(tables.elt_losses),
        jnp.asarray(tables.occ_ret), jnp.asarray(tables.occ_lim),
        jnp.asarray(tables.agg_ret), jnp.asarray(tables.agg_lim)))


def test_single_run_matches_reference(cfg, tables):
    ara = AggregateRiskAnalysis(cfg)
    np.testing.assert_allclose(ara.run_single(tables), _ref_ylt(tables),
                               rtol=1e-6)


@pytest.mark.parametrize("tenants,mode", [(1, "sequential"),
                                          (2, "sequential"),
                                          (4, "sequential"),
                                          (2, "concurrent")])
def test_tenant_chunked_equals_single(cfg, tables, tenants, mode):
    """Multi-tenancy is a pure scheduling change — results are identical."""
    ara = AggregateRiskAnalysis(
        cfg, TenancyConfig(1, tenants, mode))
    rep = ara.run_tenant_chunked(tables)
    np.testing.assert_allclose(rep.ylt, _ref_ylt(tables), rtol=1e-6)
    assert rep.wall_s > 0
    assert len(rep.per_tenant_s) == tenants


def test_straggler_reorder_preserves_results(cfg, tables):
    ara = AggregateRiskAnalysis(cfg, TenancyConfig(1, 4))
    hist = {0: 5.0, 1: 1.0, 2: 3.0, 3: 0.5}
    rep = ara.run_tenant_chunked(tables, straggler_hist=hist)
    np.testing.assert_allclose(rep.ylt, _ref_ylt(tables), rtol=1e-6)


def test_generator_determinism(cfg):
    a, b = generate(cfg, seed=7), generate(cfg, seed=7)
    np.testing.assert_array_equal(a.yet, b.yet)
    np.testing.assert_array_equal(a.elt_losses, b.elt_losses)
    c = generate(cfg, seed=8)
    assert not np.array_equal(a.yet, c.yet)


def test_generator_structure(cfg, tables):
    assert tables.elt_losses[0].max() == 0.0       # pad row zero
    assert tables.yet.min() >= 0
    assert tables.yet.max() <= cfg.event_catalog
    assert (tables.occ_lim > 0).all()


def test_paper_scale_footprints():
    # paper: YET 4 GB, ELTs 120 MB, PF ~4 MB
    sizes = paper_scale_nbytes(RiskAppConfig())
    assert 3900 < sizes["yet_mb"] < 4100
    assert 100 < sizes["elt_mb"] < 140


def test_metrics_properties(tables, cfg):
    ara = AggregateRiskAnalysis(cfg)
    ylt = jnp.asarray(ara.run_single(tables))
    p = metrics.pml(ylt)
    vals = [float(p[r]) for r in (10, 50, 100, 250, 500, 1000)]
    assert all(b >= a for a, b in zip(vals, vals[1:]))   # monotone in period
    assert float(metrics.tvar(ylt)) >= float(metrics.var(ylt))
    assert float(metrics.expected_loss(ylt)) <= float(tables.agg_lim)
    assert (np.asarray(ylt) >= 0).all()
    assert (np.asarray(ylt) <= tables.agg_lim + 1e-3).all()


def test_aggregate_terms_bound_losses(cfg, tables):
    """Every YLT entry respects min(max(l-AggR,0),AggL) bounds."""
    y = _ref_ylt(tables)
    assert y.min() >= 0.0
    assert y.max() <= tables.agg_lim + 1e-3


def test_sharded_step_single_device(cfg, tables):
    import jax
    from repro.launch.mesh import make_local_mesh
    mesh = make_local_mesh()
    ara = AggregateRiskAnalysis(cfg)
    step = ara.make_sharded_step(mesh, chunk=16)
    ylt = step(jnp.asarray(tables.yet), jnp.asarray(tables.elt_losses),
               jnp.asarray(tables.occ_ret), jnp.asarray(tables.occ_lim),
               jnp.asarray(tables.agg_ret), jnp.asarray(tables.agg_lim))
    np.testing.assert_allclose(np.asarray(ylt), _ref_ylt(tables), rtol=1e-6)


# ---------------------------------------------------------------------------
# a portfolio of layers: a catastrophe excess-of-loss tower
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tower_cfg(cfg):
    return dataclasses.replace(cfg, num_layers=8)


@pytest.fixture(scope="module")
def tower(tower_cfg):
    return generate(tower_cfg, seed=3)


def test_generated_tower_stacks_its_layers(tower_cfg, tower):
    """Each layer attaches where the one below exhausts; every term is per
    layer, and the one-layer tables are the tower's first attachment."""
    L, M = tower_cfg.num_layers, tower_cfg.num_elts
    assert tower.num_layers == L and tower.ylt_shape == (64, L)
    assert tower.occ_ret.shape == tower.occ_lim.shape == (L, M)
    assert tower.agg_ret.shape == tower.agg_lim.shape == (L,)
    np.testing.assert_allclose(tower.occ_ret[1:],
                               tower.occ_ret[:-1] + tower.occ_lim[:-1],
                               rtol=1e-6)
    assert (tower.occ_lim > 0).all() and (tower.agg_ret > 0).all()
    one = generate(RiskAppConfig().reduced(), seed=3)
    np.testing.assert_array_equal(one.yet, tower.yet)
    np.testing.assert_array_equal(one.occ_ret, tower.occ_ret[0])
    assert one.num_layers == 1 and one.ylt_shape == (64,)


def test_tenant_chunked_tower_matches_plain_reference(tower_cfg, tower):
    """``run_tenant_chunked`` prices all 8 layers in one step a tenant chunk,
    equal to the plain per-layer Algorithm 3 of the benchmark."""
    from bench.reference import risk_tower as reference
    ara = AggregateRiskAnalysis(tower_cfg, TenancyConfig(1, 2))
    ylt = ara.run_tenant_chunked(tower).ylt
    loss = reference.trial_losses(tower.yet, tower.elt_losses,
                                  tower.occ_ret, tower.occ_lim)
    want = reference.year_losses(loss, tower.agg_ret, tower.agg_lim)
    assert ylt.shape == want.shape == (64, 8)
    assert (want > 0).any(axis=0).all()
    np.testing.assert_allclose(ylt, want, rtol=1e-5,
                               atol=1e-6 * float(loss.max()))
    assert ara.trace_count == 1


def test_new_tower_terms_neither_compile_nor_upload(tower_cfg, tower):
    """The per-layer aggregate terms are a dynamic argument: a second
    analysis under new terms compiles nothing and uploads no table."""
    ara = AggregateRiskAnalysis(tower_cfg, TenancyConfig(1, 2))
    first = ara.run_tenant_chunked(tower).ylt
    traces, uploads = ara.trace_count, ara.table_uploads
    scale = np.linspace(0.5, 1.5, 8).astype(np.float32)
    what_if = dataclasses.replace(tower, agg_ret=tower.agg_ret * scale,
                                  agg_lim=tower.agg_lim * scale[::-1])
    second = ara.run_tenant_chunked(what_if).ylt
    assert (ara.trace_count, ara.table_uploads) == (traces, uploads) == (1, 1)
    assert not np.array_equal(first, second)
    np.testing.assert_allclose(
        second, ara.run_single(what_if), rtol=1e-6)
