"""shard_map expert-parallel MoE == scatter baseline (8-device subprocess)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from repro.configs import ArchConfig, MoEConfig
    from repro.distributed.sharding import Sharder
    from repro.models.moe import apply_moe_scatter, apply_moe_ep, init_moe
    from repro.models import params as pp

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    sh = Sharder(mesh, fsdp=False, seq_shard=False)
    cfg = ArchConfig(name="t", family="moe", num_layers=2, d_model=32,
        num_heads=2, num_kv_heads=2, head_dim=16, d_ff=0, vocab_size=128,
        moe_period=1,
        moe=MoEConfig(num_experts=8, top_k=2, d_ff_expert=16,
                      capacity_factor=8.0, group_size=64),
        param_dtype="float32", compute_dtype="float32")
    params, _ = pp.split(init_moe(jax.random.PRNGKey(0), cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))

    with mesh:
        y1, l1 = jax.jit(lambda p, x: apply_moe_scatter(p, x, cfg, sh))(params, x)
        y2, l2 = jax.jit(lambda p, x: apply_moe_ep(p, x, cfg, sh))(params, x)

    def loss_sc(p, x):
        y, l = apply_moe_scatter(p, x, cfg, sh)
        return jnp.sum(y ** 2) + sum(l.values())
    def loss_ep(p, x):
        y, l = apply_moe_ep(p, x, cfg, sh)
        return jnp.sum(y ** 2) + sum(l.values())
    with mesh:
        g1 = jax.jit(jax.grad(loss_sc))(params, x)
        g2 = jax.jit(jax.grad(loss_ep))(params, x)
    out = {
        "fwd_err": float(jnp.max(jnp.abs(y1 - y2))),
        "aux_err": abs(float(l1["moe_aux"]) - float(l2["moe_aux"])),
        "grad_err": max(float(jnp.max(jnp.abs(g1[k] - g2[k]))) for k in g1),
    }
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def result():
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    env.pop("REPRO_MOE_DISPATCH", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_ep_forward_matches_scatter(result):
    assert result["fwd_err"] < 5e-3


def test_ep_aux_matches(result):
    assert result["aux_err"] < 1e-6


def test_ep_grads_match(result):
    assert result["grad_err"] < 1e-3
