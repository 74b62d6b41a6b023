"""aggregate_loss Pallas kernel vs pure-jnp oracle: shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import aggregate_loss as agg
from repro.kernels.aggregate_loss import aggregate_loss_pallas
from repro.kernels.ref import aggregate_loss_chunked_ref, aggregate_loss_ref


def _case(rng, T, K, M, cat):
    ids = rng.integers(0, cat + 1, (T, K)).astype(np.int32)
    elt = np.abs(rng.normal(size=(cat + 1, M))).astype(np.float32)
    elt[0] = 0.0
    occ_r = (np.abs(rng.normal(size=M)) * 0.5).astype(np.float32)
    occ_l = (np.abs(rng.normal(size=M)) + 1.0).astype(np.float32)
    return (jnp.asarray(ids), jnp.asarray(elt), jnp.asarray(occ_r),
            jnp.asarray(occ_l), np.float32(K * 0.1), np.float32(K * 0.8))


SWEEP = [
    # T, K, M, cat, chunk (of the oracle), trial_block
    (64, 32, 3, 512, 16, 32),
    (128, 64, 5, 1000, 32, 64),
    (32, 16, 1, 100, 8, 8),
    (256, 128, 15, 4096, 128, 256),
    (17, 24, 2, 50, 8, 16),      # odd trial count
    (48, 96, 7, 333, 48, 16),    # non-pow2 catalog
]


@pytest.mark.parametrize("path", ["vmem", "xla"])
@pytest.mark.parametrize("T,K,M,cat,chunk,tb", SWEEP)
def test_pallas_matches_oracle(rng, monkeypatch, T, K, M, cat, chunk, tb,
                               path):
    """Both lookups, VMEM and (the budget lowered) XLA's gather, against the
    oracle."""
    args = _case(rng, T, K, M, cat)
    if path == "xla":
        monkeypatch.setattr(agg, "LOOKUP_VMEM_BUDGET", 0)
    assert agg.lookup_path(cat + 1) == path
    got = aggregate_loss_pallas(*args, trial_block=tb)
    want = aggregate_loss_chunked_ref(*args, chunk=chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-3)


# catalog + 1 rows not a multiple of 128, ids at row 0 and the last row
EDGE = [
    # T, K, M, cat, chunk
    (40, 200, 15, 300, 40),
    (9, 64, 4, 126, 64),
    (24, 256, 7, 1000, 128),
]


@pytest.mark.parametrize("T,K,M,cat,chunk", EDGE)
def test_vmem_lookup_edge_rows(rng, T, K, M, cat, chunk):
    assert (cat + 1) % 128 and agg.lookup_path(cat + 1) == "vmem"
    ids, *rest = _case(rng, T, K, M, cat)
    ids = ids.at[:, 0].set(0).at[:, 1].set(cat).at[0].set(cat)
    args = (ids, *rest[:3], np.float32(0), np.float32(1e9))
    got = aggregate_loss_pallas(*args)
    want = aggregate_loss_chunked_ref(*args, chunk=chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-3)


def test_xla_fallback_beyond_budget(rng, monkeypatch):
    """A catalog whose combined losses exceed the VMEM budget is looked up
    by XLA's gather, with the same answers."""
    args = _case(rng, 32, 64, 5, 700)
    want = aggregate_loss_chunked_ref(*args, chunk=32)
    monkeypatch.setattr(agg, "LOOKUP_VMEM_BUDGET", 4 * 700)
    assert agg.lookup_path(701) == "xla"
    assert agg.lookup_path(700 - 128) == "vmem"
    got = aggregate_loss_pallas(*args, trial_block=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-3)


def test_aggregate_lookup_follows_shape(monkeypatch):
    """kernels.ops names the lookup aggregate_loss runs: the reference off
    the Pallas path, else the kernel's choice from the table's size."""
    from repro.kernels import ops as kops
    prev = kops.pallas_enabled()
    try:
        kops.use_pallas(False)
        assert kops.aggregate_lookup(701) == "jnp"
        assert kops.aggregate_lookup(701, layers=8) == "jnp.layers8"
        kops.use_pallas(True)
        assert kops.aggregate_lookup(701) == "vmem"
        assert kops.aggregate_lookup(701, layers=8) == "vmem.layers8"
        monkeypatch.setattr(agg, "LOOKUP_VMEM_BUDGET", 4 * 700)
        assert kops.aggregate_lookup(701) == "xla"
        assert kops.aggregate_lookup(701 - 128) == "vmem"
    finally:
        kops.use_pallas(prev)


def test_tenant_steps_count_their_lookup():
    """Each step a two-tenant analysis dispatches counts the lookup its
    program compiled."""
    from repro.configs.risk_app import RiskAppConfig
    from repro.core.tenancy import TenancyConfig
    from repro.kernels import ops as kops
    from repro.obs.telemetry import Telemetry
    from repro.risk.analysis import AggregateRiskAnalysis
    from repro.risk.tables import generate
    cfg = RiskAppConfig().reduced()
    tables = generate(cfg, seed=0)
    want = AggregateRiskAnalysis(cfg, TenancyConfig(1, 2)).run_single(tables)
    tel = Telemetry(enabled=True)
    prev = kops.pallas_enabled()
    kops.use_pallas(True)
    try:
        ara = AggregateRiskAnalysis(cfg, TenancyConfig(1, 2), telemetry=tel)
        ylt = ara.run_tenant_chunked(tables).ylt
    finally:
        kops.use_pallas(prev)
    lookups = {k: v for k, v in tel.counter_snapshot().items()
               if k.startswith("risk.lookup.")}
    assert lookups == {"risk.lookup.vmem": 2}
    np.testing.assert_allclose(ylt, want, rtol=1e-5, atol=1e-3)


def test_ops_routes_to_the_kernel(rng):
    """kernels.ops pads the event axis to a chunk multiple and runs the
    Pallas kernel where Pallas is on, the jnp reference where it is off,
    with the same answers."""
    from repro.kernels import ops as kops
    args = _case(rng, 32, 20, 2, 128)
    want = np.asarray(aggregate_loss_chunked_ref(*args, chunk=4))
    prev = kops.pallas_enabled()
    try:
        for pallas in (True, False):
            kops.use_pallas(pallas)
            # a new function a route: traces are cached by function
            step = lambda *a: kops.aggregate_loss(*a, chunk=8)
            eqns = jax.make_jaxpr(step)(*args).jaxpr.eqns
            assert any(e.primitive.name == "pallas_call"
                       for e in eqns) == pallas
            np.testing.assert_allclose(np.asarray(step(*args)), want,
                                       rtol=1e-5, atol=1e-3)
    finally:
        kops.use_pallas(prev)


def test_chunked_ref_matches_unchunked(rng):
    args = _case(rng, 64, 64, 4, 256)
    a = aggregate_loss_ref(*args)
    for chunk in (8, 16, 32, 64):
        b = aggregate_loss_chunked_ref(*args, chunk=chunk)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_pad_event_contributes_zero(rng):
    ids = jnp.zeros((8, 16), jnp.int32)        # all pads
    elt = jnp.ones((100, 3), jnp.float32).at[0].set(0.0)
    z = aggregate_loss_pallas(ids, elt, jnp.zeros(3), jnp.full(3, 1e9),
                              np.float32(0), np.float32(1e9))
    np.testing.assert_allclose(np.asarray(z), 0.0)


def test_occurrence_and_aggregate_clipping(rng):
    # one trial, one event of loss 10; occ_ret 2, occ_lim 5 -> event loss 5
    ids = jnp.asarray([[1]], jnp.int32)
    elt = jnp.zeros((3, 1), jnp.float32).at[1, 0].set(10.0)
    y = aggregate_loss_pallas(ids, elt, jnp.asarray([2.0]),
                              jnp.asarray([5.0]), np.float32(1.0),
                              np.float32(3.0))
    # aggregate: max(5-1,0)=4, capped at 3
    np.testing.assert_allclose(np.asarray(y), [3.0])


def test_int32_vs_int64_ids_and_f32(rng):
    args = list(_case(rng, 32, 32, 3, 128))
    got32 = aggregate_loss_pallas(*args)
    args[0] = args[0].astype(jnp.int32)
    got = aggregate_loss_pallas(*args)
    np.testing.assert_allclose(np.asarray(got32), np.asarray(got))


# ---------------------------------------------------------------------------
# a portfolio of layers (a tower): terms (L, M) and (L,), a (T, L) YLT
# ---------------------------------------------------------------------------
def _tower_case(rng, T, K, M, cat, L):
    """Seeded layers over one catalog: ids with pad events (0) and the last
    row, and in every layer past the first an ELT it does not cover (a zero
    occurrence limit)."""
    ids, elt, *_ = _case(rng, T, K, M, cat)
    ids = ids.at[:, ::5].set(0).at[0, 1].set(cat)
    att = np.sort(rng.uniform(0.0, 1.5, (L + 1, M)), axis=0)
    ret, lim = att[:-1], att[1:] - att[:-1]
    lim[1:, -1] = 0.0
    agg_ret = rng.uniform(0.0, 0.1 * K, L)
    agg_lim = rng.uniform(0.2 * K, 0.6 * K, L)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    return ids, elt, f32(ret), f32(lim), f32(agg_ret), f32(agg_lim)


@pytest.mark.parametrize("L", [1, 3, 8])
@pytest.mark.parametrize("T,K,M,cat,chunk,tb", [
    (37, 96, 5, 700, 32, 16),      # ragged trials, rows not a multiple
    (64, 128, 15, 300, 128, None),
])
def test_tower_matches_oracle(rng, T, K, M, cat, chunk, tb, L):
    """The VMEM lookup prices every layer of an event in one pass: equal to
    the oracle, each layer priced alone, with uncovered ELTs, pad events and
    a ragged trial count."""
    args = _tower_case(rng, T, K, M, cat, L)
    assert agg.lookup_path(cat + 1, L) == "vmem"
    got = np.asarray(aggregate_loss_pallas(*args, trial_block=tb))
    want = np.asarray(aggregate_loss_chunked_ref(*args, chunk=chunk))
    assert got.shape == want.shape == (T, L)
    assert (want > 0).any(axis=0).all()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-3)
    one = aggregate_loss_ref(*args)
    np.testing.assert_allclose(np.asarray(one), want, rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("path,L", [("vmem", 5), ("xla", 5), ("xla", 8)])
def test_tower_paths_match_oracle(rng, path, L, monkeypatch):
    """A tower whose layers pad to a power of two prices in VMEM, and a
    table over the budget falls back to XLA's gather of it, with the same
    answers."""
    args = _tower_case(rng, 24, 64, 4, 500, L)
    if path == "xla":
        monkeypatch.setattr(agg, "LOOKUP_VMEM_BUDGET", 4 * 500)
    assert agg.lookup_path(501, L) == path
    got = aggregate_loss_pallas(*args)
    want = aggregate_loss_chunked_ref(*args, chunk=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("path", ["jnp", "vmem", "xla"])
def test_one_layer_without_the_axis_is_the_one_layer_program(rng, path,
                                                              monkeypatch):
    """``(M,)`` terms give the ``(T,)`` YLT, bit for bit the column that
    the same terms given as one layer, ``(1, M)``, give, on each path."""
    from repro.kernels import ops as kops
    ids, elt, ret, lim, agg_ret, agg_lim = _case(rng, 40, 64, 6, 400)
    if path == "xla":
        monkeypatch.setattr(agg, "LOOKUP_VMEM_BUDGET", 0)
    prev = kops.pallas_enabled()
    kops.use_pallas(path != "jnp")
    try:
        assert kops.aggregate_lookup(401) == path
        flat = np.asarray(kops.aggregate_loss(ids, elt, ret, lim, agg_ret,
                                              agg_lim, chunk=32))
        layered = np.asarray(kops.aggregate_loss(
            ids, elt, ret[None], lim[None], jnp.asarray([agg_ret]),
            jnp.asarray([agg_lim]), chunk=32))
    finally:
        kops.use_pallas(prev)
    assert flat.shape == (40,) and layered.shape == (40, 1)
    np.testing.assert_array_equal(flat, layered[:, 0])


def test_tower_budget_counts_the_padded_layers(monkeypatch):
    """The VMEM table holds 4 bytes per catalog row and padded layer: the
    paper's 2,000,001 rows take 8 MB for one layer and 64 MiB for 8 (or 5:
    layers pad to a power of two), within the budget; a 16 MiB budget
    sends the tower to XLA's gather and keeps one layer in VMEM."""
    rows = 2_000_001
    assert agg.padded_layers(5) == agg.padded_layers(8) == 8
    assert agg.table_rows(rows, 1) == 15_626
    assert agg.table_rows(rows, 8) == agg.table_rows(rows, 5) == 131_072
    assert 4 * 128 * agg.table_rows(rows, 8) == 64 << 20
    assert agg.lookup_path(rows, 8) == "vmem"
    monkeypatch.setattr(agg, "LOOKUP_VMEM_BUDGET", 16 << 20)
    assert agg.lookup_path(rows, 1) == "vmem"
    assert agg.lookup_path(rows, 8) == "xla"
    with pytest.raises(ValueError, match="1 to 128"):
        agg.padded_layers(129)


def test_tower_steps_count_their_layered_lookup():
    """On an enabled plane, each step of a tower's analysis counts the
    layered lookup its program compiled, and ``risk.analysis`` carries the
    layer count."""
    import dataclasses
    from repro.configs.risk_app import RiskAppConfig
    from repro.core.tenancy import TenancyConfig
    from repro.kernels import ops as kops
    from repro.obs.telemetry import Telemetry
    from repro.risk.analysis import AggregateRiskAnalysis
    from repro.risk.tables import generate
    cfg = dataclasses.replace(RiskAppConfig().reduced(), num_layers=8)
    tables = generate(cfg, seed=0)
    want = AggregateRiskAnalysis(cfg, TenancyConfig(1, 2)).run_single(tables)
    tel = Telemetry(enabled=True)
    prev = kops.pallas_enabled()
    kops.use_pallas(True)
    try:
        ara = AggregateRiskAnalysis(cfg, TenancyConfig(1, 2), telemetry=tel)
        ylt = ara.run_tenant_chunked(tables).ylt
    finally:
        kops.use_pallas(prev)
    lookups = {k: v for k, v in tel.counter_snapshot().items()
               if k.startswith("risk.lookup.")}
    assert lookups == {"risk.lookup.vmem.layers8": 2}
    root, = tel.spans(name="risk.analysis")
    assert root.attrs == {"layers": 8}
    assert ylt.shape == want.shape == (cfg.num_trials, 8)
    np.testing.assert_allclose(ylt, want, rtol=1e-5, atol=1e-3)


# ---------------------------------------------------------------------------
# the VMEM lookup's id blocks: rows of 128 events, whole or a row at a time
# ---------------------------------------------------------------------------
def _rows_case(rng, T, K, L, cat=700):
    """One layer's terms (``L`` None) or a tower of ``L``, with every trial's
    first event the pad row and its middle and last the last catalog row."""
    if L is None:
        ids, *rest = _case(rng, T, K, 6, cat)
    else:
        ids, *rest = _tower_case(rng, T, K, 6, cat, L)
    ids = ids.at[:, 0].set(0).at[:, -1].set(cat).at[:, K // 2].set(cat)
    return (ids, *rest)


def _vmem_grid(args, **kw):
    """The grid of the VMEM lookup that ``aggregate_loss_pallas`` runs."""
    jaxpr = jax.make_jaxpr(
        lambda *a: aggregate_loss_pallas(*a, **kw))(*args).jaxpr
    grids = [e.params["grid_mapping"].grid for e in jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert len(grids) == 1
    return grids[0]


@pytest.mark.parametrize("L", [None, 8])
@pytest.mark.parametrize("T,K,tb", [
    (13, 40, 8),        # K under 128: one row of K events
    (21, 200, 16),      # K not a multiple of 128, trials ragged
    (16, 128, None),    # one whole row
    (9, 1024, 8),       # the paper's padded K: eight rows, trials ragged
])
def test_vmem_rows_of_128_match_oracle(rng, T, K, tb, L):
    """The VMEM lookup reads a trial's ids in rows of 128 events (one row of
    K under 128), K padded with event 0 up to whole rows: equal to the
    oracle for one layer and for a tower, with the pad row and the last
    catalog row among the ids."""
    args = _rows_case(rng, T, K, L)
    assert agg.lookup_path(701, L or 1) == "vmem"
    assert _vmem_grid(args, trial_block=tb)[1] == 1     # whole rows a step
    got = np.asarray(aggregate_loss_pallas(*args, trial_block=tb))
    want = np.asarray(aggregate_loss_chunked_ref(*args,
                                                 chunk=np.gcd(K, 128)))
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("L", [None, 8])
def test_vmem_chunked_id_blocks_match_whole_rows(rng, monkeypatch, L):
    """Where a trial block's whole rows of ids do not fit the SMEM budget
    the kernel takes one row of each trial a step: the same events in the
    same order into the same lanes, so the YLT is bit for bit the one of
    whole rows, and the oracle's."""
    args = _rows_case(rng, 19, 300, L)
    whole = np.asarray(aggregate_loss_pallas(*args))
    assert _vmem_grid(args) == (1, 1)
    monkeypatch.setattr(agg, "LOOKUP_SMEM_BUDGET", 4 * 8 * 128 * 2)
    assert _vmem_grid(args) == (2, 3)       # 16 trials a block, 3 rows
    chunked = np.asarray(aggregate_loss_pallas(*args))
    np.testing.assert_array_equal(chunked, whole)
    want = np.asarray(aggregate_loss_chunked_ref(*args, chunk=100))
    np.testing.assert_allclose(chunked, want, rtol=1e-5, atol=1e-3)
