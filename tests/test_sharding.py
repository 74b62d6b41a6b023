"""Sharder logical-rule resolution + an 8-device pjit integration test run in
a subprocess (this process keeps its single CPU device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.distributed.sharding import Sharder


def test_null_sharder_is_identity():
    import jax.numpy as jnp
    sh = Sharder(None)
    x = jnp.ones((4, 4))
    assert sh.constrain(x, ("batch", None)) is x


SUBPROCESS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import Sharder
    from repro.configs import get_config
    from repro.models.model import build_model
    from repro.models import params as pp
    from repro.training.optimizer import make_optimizer
    from repro.training.train_loop import build_train_step, init_train_state

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    sh = Sharder(mesh, fsdp=True, seq_shard=False)

    out = {}
    # rule resolution: divisible dims shard, indivisible replicate
    out["heads_div"] = str(sh.spec(("fsdp", "heads"), (256, 64)))
    out["heads_indiv"] = str(sh.spec((None, "heads"), (256, 6)))
    out["kvseq_fallback"] = str(sh.spec(("batch", "kvseq"), (1, 64)))
    out["kvseq_normal"] = str(sh.spec(("batch", "kvseq"), (8, 64)))
    out["used_once"] = str(sh.spec(("heads", "ff"), (64, 64)))

    # end-to-end: reduced arch trains on the 2x4 mesh with sharded params
    cfg = get_config("internlm2-1.8b").reduced()
    bundle = build_model(cfg)
    boxed = bundle.init(jax.random.PRNGKey(0))
    params, axes = pp.split(boxed)
    from repro.distributed.sharding import param_shardings
    shards = param_shardings(sh, axes, jax.eval_shape(lambda: params))
    params = jax.tree.map(
        lambda v, s: jax.device_put(v, s) if s is not None else v,
        params, shards)
    opt = make_optimizer(cfg)
    state = init_train_state(bundle, opt, params)
    step = jax.jit(build_train_step(bundle, sh, opt))
    import numpy as np
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(1, 200, (8, 32)), jnp.int32),
             "labels": jnp.asarray(rng.integers(1, 200, (8, 32)), jnp.int32)}
    with mesh:
        state, metrics = step(state, batch)
    out["loss"] = float(metrics["loss"])
    out["finite"] = bool(jnp.isfinite(metrics["loss"]))
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def subproc_result():
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SUBPROCESS_SCRIPT],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_spec_resolution_on_mesh(subproc_result):
    o = subproc_result
    assert o["heads_div"] == "PartitionSpec('data', 'model')"
    assert o["heads_indiv"] == "PartitionSpec(None, None)"
    # batch=1 frees data; kvseq takes model (+data fallback set)
    assert "model" in o["kvseq_fallback"]
    assert o["kvseq_normal"].startswith("PartitionSpec('data',")
    # an axis is used at most once per spec
    assert o["used_once"] == "PartitionSpec('model', None)"


def test_sharded_train_step_runs(subproc_result):
    assert subproc_result["finite"]
    assert subproc_result["loss"] > 0
