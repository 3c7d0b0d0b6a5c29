"""Fig 7 (beyond paper): scaling of the mesh-sharded PB reduction.

Two legs (DESIGN.md §9):

  * **modeled** — per-device HBM bytes and interconnect bytes of the
    owner-sharded fused execution (``core/traffic.py``) at 1/2/4/8
    devices for every bench graph, at the paper's Xeon-scale inputs. The
    claim under test: per-device HBM traffic drops monotonically with
    device count, for processing and pre-processing streams alike, while
    the exchange stays interconnect-bound-or-better
    (``roofline.ShardedPBStreamRoofline``).
  * **measured** — wall-clock of ``PBExecutor.shard_reduce_stream`` on a
    forced 8-virtual-device CPU mesh (a subprocess sets
    ``--xla_force_host_platform_device_count=8``, so this runs anywhere):
    strong scaling (fixed stream, more devices) and weak scaling (fixed
    per-device stream; efficiency = t_1 / t_k, ideal 1.0). Host-device
    emulation shares one physical core, so measured CPU numbers show the
    overhead trend, not real-speedup — the modeled column is the
    hardware claim (DESIGN.md §6's measured-vs-modeled split).

A third leg covers the chunked exchange pipeline (DESIGN.md §13):

  * **overlap (modeled)** — ``ShardedPBStreamRoofline``'s overlap model
    per bench graph at paper scale: hidden-exchange fraction and overlap
    efficiency at K=4, and the model's best K under per-chunk launch
    overhead (``fig7/overlap/<graph>``).
  * **chunk sweep (measured)** — ``shard_reduce_stream`` at K ∈ {1,2,4}
    on the forced 8-device mesh, reporting measured overlap efficiency
    (t_K1 / t_K), the chosen capacity, and the modeled hidden fraction
    next to it (``fig7/chunks/k<K>``; ``fig7/chunks/auto`` is the
    decision-driven K). Host-device emulation shares one core, so the
    measured column shows schedule overhead, not real overlap — the
    modeled column is the hardware claim.

Rows: ``fig7/modeled_hbm/<graph>``, ``fig7/modeled_ici/<graph>``,
``fig7/overlap/<graph>``, ``fig7/strong/d<k>``, ``fig7/weak/d<k>``,
``fig7/chunks/k<K>``, ``fig7/chunks/auto``.
"""
from __future__ import annotations

import os
import subprocess
import sys

from benchmarks.common import PAPER_M, PAPER_N, Rows

DEVICE_SWEEP = (1, 2, 4, 8)


def _modeled_rows(rows: Rows) -> None:
    from repro.core import graph_suite, traffic
    from repro.roofline import ShardedPBStreamRoofline

    # the 5-graph suite at smoke scale fixes (n, m) shape ratios; the
    # model is evaluated at the paper's scale like every other bench
    suite = graph_suite("smoke")
    for name, g in suite.items():
        scale = PAPER_N / g.num_nodes
        n = PAPER_N
        m = int(g.num_edges * scale)
        per_dev = [
            traffic.sharded_fused_hbm_bytes_per_device(m, n, k)
            for k in DEVICE_SWEEP
        ]
        mono = all(a > b for a, b in zip(per_dev, per_dev[1:]))
        mb = "/".join(f"{b/1e6:.0f}" for b in per_dev)
        rows.add(
            f"fig7/modeled_hbm/{name}",
            0.0,
            f"per-device MB at d=1/2/4/8: {mb} monotone_decreasing={mono}",
        )
        rl = ShardedPBStreamRoofline(m, n, n_dev=DEVICE_SWEEP[-1])
        rows.add(
            f"fig7/modeled_ici/{name}",
            0.0,
            f"d=8 ici_MB={rl.ici_bytes_per_device/1e6:.0f} "
            f"bottleneck={rl.bottleneck} "
            f"speedup_ceiling={rl.speedup_ceiling:.2f}x",
        )
        rows.add(
            f"fig7/overlap/{name}",
            0.0,
            f"d=8 K=4 hidden_frac={rl.hidden_exchange_fraction(4):.3f} "
            f"overlap_eff={rl.overlap_efficiency(4):.3f} "
            f"best_K={rl.best_pipeline_chunks()} "
            f"t_seq_us={rl.t_sequential*1e6:.1f} "
            f"t_pipe4_us={rl.t_pipelined(4)*1e6:.1f}",
        )


def _child_main() -> None:
    """Runs inside the 8-virtual-device subprocess; prints result rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import SCALE, time_fn
    from repro.core import PBExecutor, make_stream_mesh

    ndev = jax.device_count()
    ex = PBExecutor()
    rng = np.random.default_rng(7)
    base_n, base_m = (1 << 12, 1 << 15) if SCALE != "full" else (1 << 15, 1 << 18)

    def reduce_on(mesh, idx, val, n):
        return ex.shard_reduce_stream(idx, val, out_size=n, mesh=mesh, op="add")

    # strong scaling: one fixed stream, 1..8 devices
    n, m = base_n * 8, base_m * 8
    idx = jnp.asarray(rng.integers(0, n, m), jnp.int32)
    val = jnp.asarray(rng.standard_normal(m), jnp.float32)
    t1 = None
    for k in DEVICE_SWEEP:
        if k > ndev:
            break
        mesh = make_stream_mesh(k)
        t = time_fn(lambda: reduce_on(mesh, idx, val, n))
        t1 = t if t1 is None else t1
        print(f"ROW,fig7/strong/d{k},{t*1e6:.1f},m={m} n={n} speedup={t1/t:.2f}x")

    # weak scaling: fixed per-device stream
    t1 = None
    for k in DEVICE_SWEEP:
        if k > ndev:
            break
        n, m = base_n * k, base_m * k
        idx = jnp.asarray(rng.integers(0, n, m), jnp.int32)
        val = jnp.asarray(rng.standard_normal(m), jnp.float32)
        mesh = make_stream_mesh(k)
        t = time_fn(lambda: reduce_on(mesh, idx, val, n))
        t1 = t if t1 is None else t1
        print(
            f"ROW,fig7/weak/d{k},{t*1e6:.1f},"
            f"m/dev={base_m} n/dev={base_n} efficiency={t1/t:.2f}"
        )

    # chunk sweep (DESIGN.md §13): measured overlap efficiency at
    # K ∈ {1, 2, 4} on the full 8-device mesh, modeled hidden-exchange
    # fraction next to it, and the chosen (estimated) capacity from the
    # decision log — the fig7 record of satellite capacity estimation
    from repro.roofline import ShardedPBStreamRoofline

    n, m = base_n * 8, base_m * 8
    idx = jnp.asarray(rng.integers(0, n, m), jnp.int32)
    val = jnp.asarray(rng.standard_normal(m), jnp.float32)
    mesh = make_stream_mesh(8)
    rl = ShardedPBStreamRoofline(m, n, n_dev=8)
    tk1 = None
    for K in (1, 2, 4):
        t = time_fn(
            lambda: ex.shard_reduce_stream(
                idx, val, out_size=n, mesh=mesh, op="add", pipeline_chunks=K
            )
        )
        tk1 = t if tk1 is None else tk1
        last = ex.decision_log[-1]
        print(
            f"ROW,fig7/chunks/k{K},{t*1e6:.1f},"
            f"measured_overlap_eff={tk1/t:.2f} "
            f"modeled_hidden_frac={rl.hidden_exchange_fraction(K):.3f} "
            f"capacity={last.get('capacity')} "
            f"overflow={last.get('overflow')} packed={last.get('packed')}"
        )
    # decision-driven K (the executor's pipeline_chunks axis)
    t = time_fn(
        lambda: ex.shard_reduce_stream(idx, val, out_size=n, mesh=mesh, op="add")
    )
    last = ex.decision_log[-1]
    print(
        f"ROW,fig7/chunks/auto,{t*1e6:.1f},"
        f"K={last.get('pipeline_chunks')} model_best_K={rl.best_pipeline_chunks()} "
        f"capacity={last.get('capacity')} source={last.get('capacity_source')}"
    )


def run() -> Rows:
    rows = Rows()
    _modeled_rows(rows)

    env = dict(os.environ)
    # extend, don't replace: keep the caller's XLA flags / import paths
    # (our device-count flag comes last, so it wins on conflict)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    # the child is a virtual-device CPU sweep (its rows say cpu): pin it
    # there, so it never contends with this process for an attached TPU
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(repo, "src")
    env["PYTHONPATH"] = (
        src + os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else src
    )
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.fig7_scaling", "--child"],
        env=env,
        cwd=repo,
        capture_output=True,
        text=True,
        timeout=1200,
    )
    if r.returncode != 0:
        raise RuntimeError(f"fig7 child failed:\n{r.stderr[-2000:]}")
    for line in r.stdout.splitlines():
        if line.startswith("ROW,"):
            _, name, us, derived = line.split(",", 3)
            rows.add(name, float(us), derived)
    return rows


if __name__ == "__main__":
    if "--child" in sys.argv[1:]:
        _child_main()
    else:
        for row in run().emit():
            print(row)
