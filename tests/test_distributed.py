"""Distribution substrate: sharding rules, collectives, pipeline parallel,
elastic replanning.  Multi-device cases run in a subprocess with forced
host device count (kept out of this process: smoke tests must see 1 device)."""

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.distributed.sharding import AxisRules, _leaf_spec
from jax.sharding import PartitionSpec as P


def run_with_devices(n: int, body: str) -> str:
    """Run `body` in a subprocess with n host devices; returns stdout.

    XLA compilation for many forced host devices is CPU-bound; on small
    CI machines it can exceed any reasonable budget, so a timeout skips
    the case instead of failing it (REPRO_DEVICE_TEST_TIMEOUT overrides).
    """
    prog = (
        f"import os\n"
        f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={n}'\n"
        + textwrap.dedent(body)
    )
    budget = int(os.environ.get("REPRO_DEVICE_TEST_TIMEOUT", "240"))
    try:
        res = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                             text=True, timeout=budget,
                             env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                                  "HOME": os.path.expanduser("~"),
                                  "JAX_PLATFORMS": "cpu"})
    except subprocess.TimeoutExpired:
        pytest.skip(f"{n}-device subprocess exceeded {budget}s on this machine")
    assert res.returncode == 0, res.stderr[-3000:]
    return res.stdout


# ---------------------------------------------------------------------------
# sharding rules (pure logic, no devices needed)


def test_divisibility_fallback():
    mesh = jax.make_mesh((1, 1), ("data", "model"))

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    rules = AxisRules.__new__(AxisRules)
    rules.mesh = FakeMesh()
    rules.mapping = {"dp": ("data",), "tp": ("model",),
                     "tp_kv": ("model",), "sp_kv": ("model",)}
    # 8 kv heads don't divide model=16 -> head dim replicated, seq takes it
    spec = rules.spec([None, "dp", "tp_kv", "sp_kv", None],
                      (95, 128, 8, 32768, 128))
    assert spec == P(None, "data", None, "model", None)
    # 64 heads divide -> heads sharded, seq left alone (dedup)
    spec = rules.spec([None, "dp", "tp_kv", "sp_kv", None],
                      (95, 128, 64, 32768, 128))
    assert spec == P(None, "data", "model", None, None)
    # nothing divides -> fully replicated but batch
    spec = rules.spec([None, "dp", "tp_kv", "sp_kv", None],
                      (95, 128, 5, 1001, 3))
    assert spec == P(None, "data", None, None, None)


def test_fsdp_param_spec():
    class FakeMesh:
        axis_names = ("pod", "data", "model")
        shape = {"pod": 2, "data": 16, "model": 16}

    # (vocab, d_model): vocab -> fsdp(32), d_model -> tp(16)
    spec = _leaf_spec((102400, 8192), FakeMesh(), ("pod", "data"), "model",
                      stacked=False)
    assert spec == P(("pod", "data"), "model")
    # stacked layer param: leading dim untouched
    spec = _leaf_spec((95, 8192, 22016), FakeMesh(), ("pod", "data"), "model",
                      stacked=True)
    assert spec[0] is None
    # 1-D params replicated
    assert _leaf_spec((8192,), FakeMesh(), ("pod", "data"), "model",
                      stacked=False) == P(None)


def test_elastic_replan():
    from repro.distributed.elastic import replan, validate_batch_divisibility
    from repro.models import build
    shapes = build("smollm-360m").param_shapes()
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    plan = replan(shapes, mesh)
    assert plan.dp_degree == 1
    ok, _ = validate_batch_divisibility(256, plan)
    assert ok


# ---------------------------------------------------------------------------
# multi-device semantics (subprocess)


def test_hierarchical_psum_equals_flat_psum():
    out = run_with_devices(8, """
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.distributed.collectives import hierarchical_psum
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        x = jnp.arange(32.0).reshape(8, 4)
        def flat(v):  return jax.lax.psum(v, ("pod", "data"))
        def hier(v):  return hierarchical_psum(v)
        sm = lambda f: shard_map(f, mesh=mesh,
                                 in_specs=P(("pod","data"), "model"),
                                 out_specs=P(("pod","data"), "model"))
        a = sm(flat)(x); b = sm(hier)(x)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
        print("PSUM_OK")
    """)
    assert "PSUM_OK" in out


def test_pipeline_parallel_matches_sequential():
    out = run_with_devices(4, """
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline_parallel import pipelined
        mesh = jax.make_mesh((4,), ("stage",))
        L, D = 8, 16
        ks = jax.random.split(jax.random.PRNGKey(0), L)
        params = jnp.stack([jax.random.normal(k, (D, D)) * 0.2 for k in ks])
        def layer(w, x): return jnp.tanh(x @ w)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, D))
        # sequential reference
        ref = x
        for i in range(L): ref = layer(params[i], ref)
        apply = pipelined(layer, mesh, "stage", n_microbatches=4)
        out = apply(params, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
        print("PP_OK")
    """)
    assert "PP_OK" in out


def test_production_mesh_shapes():
    out = run_with_devices(512, """
        from repro.launch.mesh import make_production_mesh
        m1 = make_production_mesh()
        m2 = make_production_mesh(multi_pod=True)
        assert dict(m1.shape) == {"data": 16, "model": 16}
        assert dict(m2.shape) == {"pod": 2, "data": 16, "model": 16}
        print("MESH_OK")
    """)
    assert "MESH_OK" in out
