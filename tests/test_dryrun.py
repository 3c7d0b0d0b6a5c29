"""Dry-run machinery tests on a small host mesh (subprocess-isolated so
the main pytest process keeps one device). Proves the abstract-params /
abstract-cache path, sharding rules, and roofline parsing end to end
without the 512-device compile cost."""
import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(body: str, devices: int = 8, timeout: int = 900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(body)],
        env=env, capture_output=True, text=True, timeout=timeout,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout


def test_lower_compile_small_mesh_train_and_decode():
    out = run_py("""
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType
        from repro.configs import get_config
        from repro.configs.registry import ShapeSpec
        from repro.launch.dryrun import lower_cell, device_bytes, abstract_params
        from repro.distributed import sharding as shd

        mesh = jax.make_mesh(
            (4, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2
        )
        cfg = get_config("qwen2-1.5b").reduced()
        train = ShapeSpec("t", 64, 8, "train")
        comp = lower_cell(cfg, train, mesh).compile()
        from repro import compat
        ca = compat.cost_analysis(comp)
        assert ca.get("flops", 0) > 0
        dec = ShapeSpec("d", 64, 8, "decode")
        comp2 = lower_cell(cfg, dec, mesh).compile()
        hlo = comp2.as_text()
        print("TRAIN_FLOPS", ca["flops"])
        with shd.use_mesh(mesh):
            p, _ = abstract_params(cfg, mesh)
            print("PARAM_BYTES", device_bytes(p))
    """)
    assert "TRAIN_FLOPS" in out and "PARAM_BYTES" in out


def test_collective_parser_on_real_hlo():
    out = run_py("""
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        from repro.roofline import collective_bytes_from_hlo

        mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))

        def f(x):
            y = jax.lax.with_sharding_constraint(
                x @ x, NamedSharding(mesh, P(None, None)))
            return y.sum()

        x_sds = jax.ShapeDtypeStruct((512, 512), jnp.float32,
                                     sharding=NamedSharding(mesh, P("data", None)))
        comp = jax.jit(f).lower(x_sds).compile()
        coll = collective_bytes_from_hlo(comp.as_text())
        print("COLL", coll["total"])
        assert coll["total"] > 0  # resharding needs an all-gather
    """)
    assert "COLL" in out


def test_extrapolation_math():
    from repro.roofline import CellCost, extrapolate

    a = CellCost(flops=10.0, bytes_accessed=100.0, collective={"total": 4.0}, num_layers=2)
    b = CellCost(flops=18.0, bytes_accessed=160.0, collective={"total": 8.0}, num_layers=4)
    f = extrapolate(a, b, 10)
    assert f.flops == 10.0 + 4.0 * 8  # per-layer 4 flops
    assert f.bytes_accessed == 100.0 + 30.0 * 8
    assert f.collective["total"] == 4.0 + 2.0 * 8


def test_shape_bytes_parser():
    from repro.roofline import collective_bytes_from_hlo, shape_bytes

    assert shape_bytes("f32", "4,4") == 64
    assert shape_bytes("bf16", "10") == 20
    hlo = """
      %ar = f32[128,256]{1,0} all-reduce(%x), replica_groups={}
      %ag.1 = (bf16[64]{0}, bf16[32]{0}) all-gather(%a, %b), dimensions={0}
      %done = f32[8]{0} all-reduce-done(%start)
    """
    got = collective_bytes_from_hlo(hlo)
    assert got["all-reduce"] == 128 * 256 * 4
    assert got["all-gather"] == (64 + 32) * 2
    assert got["total"] == got["all-reduce"] + got["all-gather"]


def test_cells_enumeration_covers_assignment():
    from repro.configs.registry import SHAPES, cells

    all_cells = cells(include_skipped=True)
    assert len(all_cells) == 40  # 10 archs x 4 shapes
    runnable = [c for c in all_cells if c[2] is None]
    assert len(runnable) == 32  # 8 long_500k skips documented
    skipped = [c for c in all_cells if c[2] is not None]
    assert all(s[1] == "long_500k" for s in skipped)
    assert {s[0] for s in skipped} == {
        "llama-3.2-vision-11b", "qwen2-1.5b", "deepseek-7b", "qwen2.5-14b",
        "phi3-medium-14b", "whisper-base", "qwen3-moe-235b-a22b",
        "llama4-maverick-400b-a17b",
    }
