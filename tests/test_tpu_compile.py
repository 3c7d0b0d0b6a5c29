"""The fused C-Buffer kernels compile for a TPU v5e (no chip needed).

Each test lowers and compiles for a *described* v5e chip
(``jax.experimental.topologies``) at the width the chip runs: the
GAP-urand-shaped graph of 2^20 vertices and 16 edges per vertex, and
F=128 feature rows. What the Mosaic compiler refuses here (an
unsupported primitive, a tiling it cannot lay out, more VMEM than the
kernel may use) fails the test without spending chip time. The sharded
CSR build and PageRank compile for the four chips of the described 2x2
host. The topology is described inside a fixture, never at import, so
every pytest worker collects the same tests and only the worker running
this file loads the TPU compiler.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core import executor as executor_mod
from repro.core.executor import PBExecutor, execute_reduce
from repro.core.plan import FUSED_BIN_RANGE, HardwareModel, fused_fits, fused_vmem_bytes
from repro.kernels.fused import (
    cobra_bin_accumulate_pallas,
    cobra_bin_accumulate_rows_pallas,
)

N_URAND = 1 << 20  # GAP urand-shaped graph at scale 20
M_URAND = N_URAND * 16
N_ROWS, F_ROWS, M_ROWS = 1 << 16, 128, 1 << 20
VMEM_BUDGET = HardwareModel.tpu_v5e().fast_levels[-1]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.asarray(topo.devices[:4]), ("shard",))


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Code that resolves ``interpret`` itself sees the CPU backend here;
    steer it to the compiled kernels, as it runs on the chip."""
    monkeypatch.setattr(executor_mod, "resolve_interpret", lambda interpret=None: False)


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _largest_fitting(hw, **geometry) -> int:
    """The largest whole-bin domain ``fused_fits`` accepts on ``hw``."""
    bins = 1
    while fused_fits(hw, (bins + 1) * FUSED_BIN_RANGE, **geometry):
        bins += 1
    return bins * FUSED_BIN_RANGE


@pytest.mark.parametrize("op", ["add", "min", "max"])
def test_scalar_fused_kernel_compiles_for_v5e(one_chip, op):
    assert fused_vmem_bytes(N_URAND) <= VMEM_BUDGET

    def fn(idx, val):
        return cobra_bin_accumulate_pallas(
            idx, val, num_indices=N_URAND, op=op, interpret=False,
            vmem_limit_bytes=VMEM_BUDGET,
        )

    text = _compiled_text(
        fn, one_chip, ((M_URAND,), jnp.int32), ((M_URAND,), jnp.float32)
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("op", ["add", "max"])
def test_rows_fused_kernel_compiles_for_v5e(one_chip, op):
    assert fused_vmem_bytes(N_ROWS, f_tile=F_ROWS) <= VMEM_BUDGET

    def fn(idx, val):
        return cobra_bin_accumulate_rows_pallas(
            idx, val, num_indices=N_ROWS, op=op, interpret=False,
            vmem_limit_bytes=VMEM_BUDGET,
        )

    text = _compiled_text(
        fn, one_chip, ((M_ROWS,), jnp.int32), ((M_ROWS, F_ROWS), jnp.float32)
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_execute_reduce_fused_runs_the_kernel_on_v5e(one_chip, dtype):
    """The executor's fused path, compiled (``interpret=False``), is the
    Pallas kernel at this width — not the jnp sweep."""

    def fn(idx, val):
        return execute_reduce(
            idx, val, out_size=N_URAND, op="add", method="fused", interpret=False
        )

    text = _compiled_text(fn, one_chip, ((M_URAND,), jnp.int32), ((M_URAND,), dtype))
    assert "tpu_custom_call" in text


def test_batched_fused_lanes_run_the_kernel_on_v5e(one_chip):
    """A batched fused reduce the chip compiles (two BFS-style ``min``
    lanes at the Kronecker scale-22 width) is one kernel call per lane."""
    from repro.core.executor import _jitted_reduce_batched

    n, lanes, m = 1 << 22, 2, 1 << 20
    fn = _jitted_reduce_batched(
        n, FUSED_BIN_RANGE, None, "fused", "min", 2048, False, None, VMEM_BUDGET,
    )
    text = _compiled_text(
        fn, one_chip, ((lanes, m), jnp.int32), ((lanes, m), jnp.int32)
    )
    assert text.count("tpu_custom_call") >= lanes


def _v5e_executor(tmp_path) -> PBExecutor:
    """The executor as it decides on a v5e: its model, compiled kernels."""
    return PBExecutor(
        hw=HardwareModel.tpu_v5e(), interpret=False, cache_dir=str(tmp_path)
    )


def test_fused_decision_at_the_budget_boundary_compiles(one_chip, tmp_path):
    """The one VMEM budget: the largest domain ``fused_fits`` accepts is
    decided ``fused``, names the kernel as its realization, and Mosaic
    compiles the kernel there at that limit; one bin more is not fused."""
    n = _largest_fitting(HardwareModel.tpu_v5e())
    m = 1 << 20
    ex = _v5e_executor(tmp_path)
    d = ex.decide(n, m, jnp.float32, kind="reduce", op="add")
    assert (d.method, ex.decision_log[-1]["realization"]) == ("fused", "pallas")
    over = ex.decide(n + FUSED_BIN_RANGE, m, jnp.float32, kind="reduce", op="add")
    assert over.method != "fused"

    def fn(idx, val):
        return execute_reduce(
            idx, val, out_size=n, op="add", method="fused", interpret=False,
            vmem_budget=VMEM_BUDGET,
        )

    text = _compiled_text(fn, one_chip, ((m,), jnp.int32), ((m,), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "n, feat, tile",
    [
        (1 << 16, 256, 128),  # 256 columns do not fit, 128 do
        (1 << 22, 2, 1),  # two-lane PPR on kron22: one column at a time
    ],
)
def test_row_block_decision_tile_compiles_for_v5e(one_chip, tmp_path, n, feat, tile):
    """A row block is decided fused at the widest tile that fits, names
    the kernel as its realization, and that kernel compiles."""
    m = 1 << 16
    ex = _v5e_executor(tmp_path)
    d = ex.decide(n, m, jnp.float32, kind="reduce", op="add", feature_dim=feat)
    assert (d.method, d.f_tile) == ("fused", tile)
    assert ex.decision_log[-1]["realization"] == "pallas"

    def fn(idx, val):
        return execute_reduce(
            idx, val, out_size=n, op="add", method="fused", interpret=False,
            f_tile=d.f_tile, vmem_budget=VMEM_BUDGET,
        )

    text = _compiled_text(fn, one_chip, ((m,), jnp.int32), ((m, feat), jnp.float32))
    assert text.count("tpu_custom_call") >= (feat if tile == 1 else 1)


def test_sharded_csr_and_pagerank_compile_for_four_v5e(four_chips, compiled_kernels):
    """The ``chip_smoke.py --chips 4`` programs on the described 2x2
    host: the sharded degree count and PageRank run the fused kernel as
    each device's local reduce, and every program exchanges over the
    interconnect (all-to-all)."""
    from repro.core import distributed_pb as dpb
    from repro.core.pagerank import _pr_sharded_fn

    mesh, axis, nd = four_chips, "shard", 4
    n = 1 << 18
    m = n * 16
    r = dpb.shard_range_for(n, nd)
    k, cap = dpb._chunk_layout(m // nd, dpb.default_pipeline_chunks(m, n, nd))
    edge = NamedSharding(mesh, PartitionSpec(axis))
    whole = NamedSharding(mesh, PartitionSpec(None))

    def text(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=sh) for s, d, sh in shapes]
        return fn.lower(*args).compile().as_text()

    edges = (((m,), jnp.int32, edge), ((m,), jnp.int32, edge))
    degrees = dpb._jitted_shard_reduce(
        mesh, axis, n, "add", "fused", r, nd, cap, k, 2048, FUSED_BIN_RANGE,
        None, True, False,
    )
    csr = dpb._jitted_shard_csr(mesh, axis, n, r, nd, cap, k, 2048, True)
    pagerank = _pr_sharded_fn(
        mesh, axis, n, nd, r, 10, "fused", 2048, cap, k, FUSED_BIN_RANGE, None
    )
    ranks = (((n,), jnp.float32, whole), ((n,), jnp.float32, whole))
    for name, program in (
        ("degrees", text(degrees, *edges)),
        ("pagerank", text(pagerank, *edges, *ranks)),
    ):
        assert "tpu_custom_call" in program, name
        assert "all-to-all" in program, name
    assert "all-to-all" in text(csr, *edges)


def test_kron24_pagerank_fits_one_v5e(one_chip, tmp_path):
    """``kron24-pagerank1``'s program: ``_pr_fused`` at the decision the
    executor makes on a v5e for Graph500 scale 24 (16,777,216 vertices,
    536,870,912 arcs), past the fused kernel's VMEM budget, compiles for
    one chip within its 15.75 GiB of HBM. A later change to that decision,
    or to the two-phase reduce it runs, that pushes the cell out of HBM
    fails here and not on the chip."""
    from repro.core.pagerank import _pr_fused

    n, m = 1 << 24, 1 << 29
    ex = _v5e_executor(tmp_path)
    d = ex.decide(n, m, jnp.float32, kind="reduce")
    assert d.method == "counting"

    def fn(src, dst):
        return _pr_fused(src, dst, n, 1, d.method, d.bin_range, d.num_bins, ex.block, d.plan)

    args = [jax.ShapeDtypeStruct((m,), jnp.int32, sharding=one_chip)] * 2
    mem = jax.jit(fn).lower(*args).compile().memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert used <= int(15.75 * (1 << 30))
