"""Multi-device semantics tests, run in subprocesses so the forced device
count cannot leak into (or be blocked by) the main test process's jax.

Covers the places where the distributed path must equal the host math:
  1. federated_solve (one psum over the mesh) == core.analytic host solve.
  2. shard_map MoE FFN == the single-program dense path.
  3. the Gram kernel on mesh-sharded rows == the one-device fold.
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(script: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = REPO_SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_federated_solve_matches_host_analytic():
    _run("""
    import jax, numpy as np
    import jax.numpy as jnp
    from repro.core import analytic as al, streaming
    from repro.core.distributed import make_federated_solve

    assert len(jax.devices()) == 8
    mesh = jax.make_mesh((4, 2), ("data", "model"))
    rng = np.random.default_rng(0)
    d, c, n_per, K = 32, 8, 64, 4   # one client cohort per 'data' shard
    xs = [rng.standard_normal((n_per, d)).astype(np.float32) for _ in range(K)]
    ys = [np.eye(c, dtype=np.float32)[rng.integers(0, c, n_per)] for _ in range(K)]

    # host reference: paper Algorithm 1 (pairwise AA + RI)
    ups = [al.local_stage(x, y, gamma=1.0) for x, y in zip(xs, ys)]
    w_ref = al.afl_aggregate(ups, use_ri=True, pairwise=True)

    # device path: per-shard raw Gram stats → ONE all-reduce + solve
    states = [streaming.update_state(streaming.init_state(d, c),
                                     jnp.asarray(x), jnp.asarray(y))
              for x, y in zip(xs, ys)]
    stacked = jax.tree.map(lambda *l: jnp.stack(l), *states)
    solve = make_federated_solve(mesh, axis_names=("data",), gamma=1.0)
    w = np.asarray(solve(stacked))
    err = np.abs(w - w_ref).max()
    assert err < 5e-4, f"device/host mismatch: {err}"
    print("ok", err)
    """)


def test_shard_map_moe_matches_dense():
    _run("""
    import jax, numpy as np
    import jax.numpy as jnp
    from repro.config import MoEConfig
    from repro.core import act
    from repro.models import moe as M

    mesh = jax.make_mesh((2, 4), ("data", "model"))
    moe = MoEConfig(num_experts=4, top_k=2, capacity_factor=2.0, group_size=16)
    d, ff = 32, 64
    p = M.init_moe(jax.random.key(0), d, ff, moe, "swiglu", jnp.float32)
    x = jax.random.normal(jax.random.key(1), (4, 32, d), jnp.float32)

    ref, aux_ref = M.moe_apply(p, x, moe, "swiglu")           # dense path

    def run(p, x):
        with act.activation_policy(mesh, ("data",), ("model",)):
            return M.moe_apply(p, x, moe, "swiglu")

    out, aux = jax.jit(run)(p, x)                              # shard_map path
    err = float(jnp.abs(out - ref).max())
    assert err < 1e-5, err
    assert abs(float(aux) - float(aux_ref)) < 1e-6
    print("ok", err)
    """)


def test_analytic_train_step_multidevice_lowering():
    """The production train step lowers + runs on a real (tiny) mesh."""
    _run("""
    import jax, numpy as np
    import jax.numpy as jnp
    from repro.configs.registry import get_config
    from repro.core import act, streaming
    from repro.launch import mesh as MM, sharding as SH, steps as ST
    from repro.launch.inputs import sample_batch
    from repro.models import transformer as T

    mesh = MM.auto_mesh((4, 2), ("data", "model"))
    cfg = get_config("granite_moe_3b_a800m").reduced(num_classes=8)
    params = T.init_params(jax.random.key(0), cfg)
    state = streaming.init_state(cfg.d_model, cfg.num_classes)
    batch = sample_batch(cfg, 8, 32, seed=0)

    def step(params, state, batch):
        with act.activation_policy(mesh, MM.batch_axes(mesh),
                                   MM.model_axes(mesh)):
            return ST.make_analytic_train_step(cfg)(params, state, batch)

    p_sh = SH.param_shardings(jax.eval_shape(lambda: params), mesh)
    b_sh = SH.batch_shardings(cfg, jax.eval_shape(lambda: batch), mesh)
    st_sh = SH.state_shardings(mesh)
    fn = jax.jit(step, in_shardings=(p_sh, st_sh, b_sh), out_shardings=st_sh)
    out = fn(params, state, batch)
    g = np.asarray(out.gram)
    assert out.gram.shape == (cfg.d_model, cfg.d_model)
    assert np.isfinite(g).all() and float(out.count) == 8 * 1
    # vs single-device reference
    ref = ST.make_analytic_train_step(cfg)(params, state, batch)
    err = np.abs(g - np.asarray(ref.gram)).max() / max(np.abs(g).max(), 1)
    assert err < 5e-5, err
    print("ok", err)
    """)


def test_gram_kernel_folds_sharded_rows_per_device():
    """A Mosaic kernel cannot be partitioned by XLA, so ops.gram_update
    folds rows sharded over a mesh on each device and psums the partials;
    the replicated result equals the one-device fold."""
    _run("""
    import jax, numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.kernels import ops
    from repro.launch import mesh as MM

    mesh = MM.auto_mesh((4, 2), ("data", "model"))
    rng = np.random.default_rng(0)
    x = rng.standard_normal((64, 48)).astype(np.float32)
    y = np.eye(5, dtype=np.float32)[rng.integers(0, 5, 64)]
    rows = NamedSharding(mesh, P("data"))
    g, q = ops.gram_update(jax.device_put(x, rows), jax.device_put(y, rows))
    assert len(g.sharding.device_set) == 8, g.sharding
    g1, q1 = ops.gram_update(jnp.asarray(x), jnp.asarray(y))
    np.testing.assert_allclose(np.asarray(g), np.asarray(g1), rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(q), np.asarray(q1), rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(g), x.T.astype(np.float64) @ x,
                               rtol=1e-5, atol=1e-4)
    # a stream of batches reuses one compiled fold
    ops.gram_update(jax.device_put(x, rows), jax.device_put(y, rows))
    info = ops._sharded_gram.cache_info()
    assert (info.misses, info.hits) == (1, 1), info
    print("ok")
    """)
