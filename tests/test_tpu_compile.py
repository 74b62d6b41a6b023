"""The main path's Pallas kernels compile for a described TPU v5e chip at the
widths they run at: the paged decode read and the admission scatter at
internlm2-1.8b widths, the aggregate-loss kernel at the paper's risk widths
(1000 events per trial, 15 ELTs, a 2,000,000-event catalog: the VMEM lookup
for one layer and for a tower of 8, with whole rows of ids a step or, the
SMEM budget lowered, one row a trial, XLA's gather with the VMEM budget
lowered, and the risk step's entry point in ``kernels.ops``) and the SSD
scan at mamba2-2.7b widths.

Nothing runs: the TPU compiler checks tiling, VMEM use and lowering, which
interpret mode on the CPU cannot.  The topology is described inside a
fixture, so only the test process that runs these tests loads the TPU
library, and the tests skip where it cannot be described.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.risk_app import CONFIG as RISK
from repro.kernels import aggregate_loss as agg
from repro.kernels import paged_attention as pa
from repro.kernels import ssd_scan

ARCH = get_config("internlm2-1.8b")
SSM_ARCH = get_config("mamba2-2.7b")
CAPACITY, PAGE, MAX_PROMPT = 8, 16, 1024
PAGES = 2 + CAPACITY * (MAX_PROMPT // PAGE)      # reserved + one ring a slot
RISK_TRIALS = 65_536                             # one tenant's chunk


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, sharding, *shapes, kernel: bool = True):
    """Compile ``fn`` for ``sharding``'s chip; ``kernel``: whether the
    program must hold a Pallas kernel (or must hold none)."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == kernel
    return compiled


def test_paged_decode_compiles(one_chip, no_compile_cache):
    H, Hkv, D = ARCH.num_heads, ARCH.num_kv_heads, ARCH.head_dim
    bf16 = jnp.bfloat16
    _compile(lambda *a: pa.paged_attention_decode_pallas(*a, interpret=False),
             one_chip,
             ((CAPACITY, H, D), bf16), ((PAGES, PAGE, Hkv, D), bf16),
             ((PAGES, PAGE, Hkv, D), bf16), ((PAGES, PAGE), jnp.int32),
             ((CAPACITY, MAX_PROMPT // PAGE), jnp.int32),
             ((CAPACITY,), jnp.int32))


def test_paged_scatter_compiles(one_chip, no_compile_cache):
    L, Hkv, D = ARCH.num_layers, ARCH.num_kv_heads, ARCH.head_dim
    nb = MAX_PROMPT // PAGE
    _compile(lambda *a: pa.paged_prefill_scatter_pallas(*a, interpret=False),
             one_chip,
             ((L, PAGES, PAGE, Hkv, D), jnp.bfloat16), ((nb,), jnp.int32),
             ((L, nb, PAGE, Hkv, D), jnp.bfloat16))


def _risk_shapes(K, layers):
    """The step's arguments at the paper's widths: a tenant's (trials, K)
    ids, the ELT, and the terms of one layer (``layers`` None) or a tower."""
    M = RISK.num_elts
    lay = () if layers is None else (layers,)
    return (((RISK_TRIALS, K), jnp.int32),
            ((RISK.event_catalog + 1, M), jnp.float32),
            (lay + (M,), jnp.float32), (lay + (M,), jnp.float32),
            (lay, jnp.float32), (lay, jnp.float32))


def _compile_aggregate_loss(sharding, kernel=True, layers=None):
    """``layers``: a tower's layer count (terms with a layer axis), or None
    for one layer's terms."""
    K = -(-RISK.events_per_trial // RISK.chunk_events) * RISK.chunk_events
    compiled = _compile(
        lambda *a: agg.aggregate_loss_pallas(*a, interpret=False),
        sharding, *_risk_shapes(K, layers), kernel=kernel)
    # the whole program, any relaid table copy included, fits one chip
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    return mem


def test_aggregate_loss_compiles(one_chip, no_compile_cache):
    mem = _compile_aggregate_loss(one_chip)
    # the VMEM lookup: no lane-padded copy of the 120 MB ELT, only the
    # combined occurrence losses (8 MB) and the YLT
    assert agg.lookup_path(RISK.event_catalog + 1) == "vmem"
    assert mem.temp_size_in_bytes < 64e6


@pytest.mark.parametrize("layers", [None, 8])
def test_risk_step_compiles(one_chip, no_compile_cache, layers):
    """``kernels.ops.aggregate_loss`` as the risk step calls it: the
    unpadded 1,000 events a trial, padded to a chunk multiple in the step,
    one Pallas kernel, the VMEM lookup, for one layer and a tower of 8."""
    from repro.kernels import ops as kops
    prev = kops.pallas_enabled()
    kops.use_pallas(True, interpret=False)
    try:
        assert kops.aggregate_lookup(RISK.event_catalog + 1, layers) == (
            "vmem" if layers is None else f"vmem.layers{layers}")
        compiled = _compile(
            lambda *a: kops.aggregate_loss(*a, chunk=RISK.chunk_events),
            one_chip, *_risk_shapes(RISK.events_per_trial, layers))
    finally:
        kops.use_pallas(prev)
    assert compiled.as_text().count("tpu_custom_call") == 1
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_tower_lookup_compiles(one_chip, no_compile_cache):
    """A tower of 8 layers at the paper's widths: every layer of an event
    in one VMEM lookup, the 64 MiB table within the budget, and no copy of
    the table padded to 128 lanes a catalog row (1 GB)."""
    assert agg.lookup_path(RISK.event_catalog + 1, 8) == "vmem"
    mem = _compile_aggregate_loss(one_chip, layers=8)
    assert mem.output_size_in_bytes == 4 * RISK_TRIALS * 8
    assert mem.temp_size_in_bytes < 200e6


@pytest.mark.parametrize("layers", [None, 8])
def test_vmem_chunked_id_blocks_compile(one_chip, no_compile_cache,
                                        monkeypatch, layers):
    """With an SMEM budget under eight trials' whole rows of ids, the VMEM
    lookup takes one row of 128 ids a trial a step, at the paper's widths,
    and still reads the ids where they lie, with no copy of them."""
    monkeypatch.setattr(agg, "LOOKUP_SMEM_BUDGET", 16 << 10)
    K = -(-RISK.events_per_trial // RISK.chunk_events) * RISK.chunk_events
    assert 4 * 8 * K > agg.LOOKUP_SMEM_BUDGET
    assert agg.lookup_path(RISK.event_catalog + 1, layers or 1) == "vmem"
    mem = _compile_aggregate_loss(one_chip, layers=layers)
    assert mem.temp_size_in_bytes < (64e6 if layers is None else 200e6)


def test_aggregate_loss_xla_fallback_compiles(one_chip, no_compile_cache,
                                              monkeypatch):
    monkeypatch.setattr(agg, "LOOKUP_VMEM_BUDGET", 1 << 20)
    assert agg.lookup_path(RISK.event_catalog + 1) == "xla"
    _compile_aggregate_loss(one_chip, kernel=False)


def test_tower_xla_fallback_compiles(one_chip, no_compile_cache, monkeypatch):
    monkeypatch.setattr(agg, "LOOKUP_VMEM_BUDGET", 16 << 20)
    assert agg.lookup_path(RISK.event_catalog + 1, 8) == "xla"
    _compile_aggregate_loss(one_chip, kernel=False, layers=8)


def test_ssd_scan_compiles(one_chip, no_compile_cache):
    ssm = SSM_ARCH.ssm
    H = SSM_ARCH.d_model * ssm.expand // ssm.head_dim
    L, P, N = 2 * ssm.chunk_size, ssm.head_dim, ssm.d_state
    bf16, f32 = jnp.bfloat16, jnp.float32
    _compile(lambda *a: ssd_scan.ssd_chunked_pallas(
                 *a, chunk=ssm.chunk_size, interpret=False),
             one_chip,
             ((1, L, H, P), bf16), ((1, L, H), f32), ((1, L, H), f32),
             ((1, L, H, N), bf16), ((1, L, H, N), bf16))
