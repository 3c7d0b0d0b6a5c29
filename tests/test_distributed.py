"""Distributed-feature tests. Each test runs in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main pytest
process keeps its single CPU device (per the dry-run isolation rule).
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(body: str, devices: int = 8, timeout: int = 600):
    code = textwrap.dedent(body)
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=timeout,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


def test_sharded_train_step_matches_single_device():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_host_mesh
        from repro.models import transformer as T
        from repro.models.params import unbox
        from repro.train.optimizer import OptConfig, init_opt_state
        from repro.train.steps import TrainState, make_batch, make_train_step
        from repro.configs.registry import ShapeSpec

        cfg = get_config("qwen2-1.5b").reduced()
        sh = ShapeSpec("s", 32, 4, "train")
        params, _ = unbox(T.init_params(jax.random.PRNGKey(0), cfg))
        oc = OptConfig(kind="adamw", warmup_steps=1, total_steps=4)
        step = make_train_step(cfg, oc)
        batch = make_batch(cfg, sh, seed=7)
        # single device
        s1 = TrainState(params, init_opt_state(params, oc))
        s1, m1 = jax.jit(step)(s1, batch)
        # 4x2 mesh
        mesh = make_host_mesh(4, 2)
        with shd.use_mesh(mesh):
            s2 = TrainState(params, init_opt_state(params, oc))
            s2, m2 = jax.jit(step)(s2, batch)
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-4)
        # params identical after one step
        l1 = jax.tree.leaves(s1.params); l2 = jax.tree.leaves(s2.params)
        for a, b in zip(l1, l2):
            np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                       rtol=2e-3, atol=2e-3)
        print("sharded == single-device OK")
    """)


def test_moe_pb_dispatch_sharded_matches_dense_oracle():
    run_py("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_host_mesh
        import repro.models.layers as L
        from repro.models.params import unbox

        cfg = get_config("qwen3-moe-235b-a22b").reduced()
        p, _ = unbox(L.init_moe(jax.random.PRNGKey(1), cfg))
        x = jax.random.normal(jax.random.PRNGKey(2), (4, 16, cfg.d_model))
        y_dense = L.moe_apply(p, x, dataclasses.replace(cfg, moe_dispatch="dense"))
        mesh = make_host_mesh(2, 4)  # experts sharded 4-way
        with shd.use_mesh(mesh):
            y_pb = jax.jit(lambda p, x: L.moe_apply(p, x, cfg))(p, x)
        np.testing.assert_allclose(np.asarray(y_pb), np.asarray(y_dense), atol=1e-4)
        print("sharded PB dispatch == dense oracle OK")
    """)


def test_gradient_compression_error_feedback():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_host_mesh
        from repro.distributed.compression import compressed_psum_tree, init_residuals

        mesh = make_host_mesh(8, 1)
        g = {"w": jax.random.normal(jax.random.PRNGKey(0), (64, 64))}
        r = init_residuals(g)
        # mean over 8 identical replicas == g itself
        out, r2 = compressed_psum_tree(g, r, mesh, axes=("data",))
        err1 = float(jnp.abs(out["w"] - g["w"]).max())
        assert err1 < 0.05, f"int8 quantization error too large: {err1}"
        # error feedback: applying twice with residual reduces accumulated bias
        out2, r3 = compressed_psum_tree(g, r2, mesh, axes=("data",))
        two_step = (out["w"] + out2["w"]) / 2
        err2 = float(jnp.abs(two_step - g["w"]).max())
        assert err2 < err1 + 1e-6, (err1, err2)
        print("compression OK", err1, err2)
    """)


def test_gpipe_pipeline_matches_sequential():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.distributed.pipeline import gpipe_apply

        mesh = jax.make_mesh((4,), ("pipe",), axis_types=(AxisType.Auto,))
        P_st, M, mb, d = 4, 8, 2, 16
        ks = jax.random.split(jax.random.PRNGKey(0), P_st)
        stage_params = {"w": jnp.stack([jax.random.normal(k, (d, d)) * 0.3 for k in ks])}

        def stage_fn(p, x):
            return jnp.tanh(x @ p["w"])

        x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, d))
        y_pipe = gpipe_apply(stage_fn, stage_params, x, mesh)
        y_seq = x
        for s in range(P_st):
            y_seq = stage_fn({"w": stage_params["w"][s]}, y_seq)
        np.testing.assert_allclose(np.asarray(y_pipe), np.asarray(y_seq), atol=1e-5)
        print("gpipe == sequential OK")
    """)


def test_elastic_checkpoint_restore_across_meshes():
    run_py("""
        import os, tempfile
        import jax, jax.numpy as jnp, numpy as np
        from repro.checkpoint.manager import CheckpointManager
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_host_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P

        tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
                "b": jnp.ones((8,), jnp.float32)}
        mesh8 = make_host_mesh(8, 1)
        tree8 = jax.tree.map(
            lambda x: jax.device_put(x, NamedSharding(mesh8, P("data"))), tree)
        with tempfile.TemporaryDirectory() as d:
            cm = CheckpointManager(d, keep_n=2)
            cm.save(10, tree8, blocking=True)
            # restore onto a DIFFERENT (4x2) mesh
            mesh4 = make_host_mesh(4, 2)
            sh = {"w": NamedSharding(mesh4, P("data", "model")),
                  "b": NamedSharding(mesh4, P("model"))}
            restored, step = cm.restore(tree, shardings=sh)
            assert step == 10
            np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(tree["w"]))
            assert restored["w"].sharding.mesh.shape == {"data": 4, "model": 2}
        print("elastic restore OK")
    """)


def test_straggler_and_heartbeat():
    from repro.ft.resilience import Heartbeat, StragglerDetector

    sd = StragglerDetector(patience=3)
    for t in range(20):
        for h in range(4):
            dt = 1.0 if h != 2 else (1.0 if t < 10 else 3.0)
            sd.observe(f"h{h}", dt)
    assert sd.flagged() == ["h2"]

    import time

    fired = []
    hb = Heartbeat(timeout_s=0.3, on_timeout=lambda: fired.append(1)).start()
    for _ in range(3):
        time.sleep(0.1)
        hb.beat()
    assert not fired
    time.sleep(0.6)
    assert fired
    hb.stop()


def test_elastic_plan_math():
    from repro.ft.resilience import ElasticPlan

    p = ElasticPlan(old_data=16, old_model=16, surviving_devices=192)
    assert p.mesh_shape() == (12, 16)
    assert p.accumulation_steps(1) == 2  # 16/12 -> ceil(1.33) = 2
    with pytest.raises(RuntimeError):
        ElasticPlan(old_data=16, old_model=16, surviving_devices=8)


def test_ddp_profile_replicates_weights_and_shards_batch():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh(2, 4)
        with shd.use_mesh(mesh, rules=shd.rules_for_profile("ddp")):
            spec_w = shd.spec_for(mesh, (64, 128), ("embed", "mlp"))
            assert spec_w == jax.sharding.PartitionSpec(None, None), spec_w
            spec_b = shd.spec_for(mesh, (8, 16), ("batch", None))
            # batch spans data AND model axes under ddp
            assert spec_b[0] == ("data", "model"), spec_b
        print("ddp profile OK")
    """)


def test_weight_stationary_moe_decode_matches_oracle():
    run_py("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_host_mesh
        import repro.models.layers as L
        from repro.models.params import unbox

        cfg = dataclasses.replace(
            get_config("qwen3-moe-235b-a22b").reduced(),
            moe_weight_stationary_decode=True)
        p, _ = unbox(L.init_moe(jax.random.PRNGKey(1), cfg))
        x = jax.random.normal(jax.random.PRNGKey(2), (4, 1, cfg.d_model))
        y_dense = L.moe_apply(p, x, dataclasses.replace(cfg, moe_dispatch="dense"))
        mesh = make_host_mesh(2, 4)
        with shd.use_mesh(mesh):
            y_ws = jax.jit(lambda p, x: L.moe_apply(p, x, cfg))(p, x)
        np.testing.assert_allclose(np.asarray(y_ws), np.asarray(y_dense), atol=1e-4)
        print("weight-stationary OK")
    """)
