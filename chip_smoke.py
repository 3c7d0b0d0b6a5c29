#!/usr/bin/env python3
"""Bring-up smoke run of the PB graph engine on a TPU.

One chip (the default) drives the serving path once, through the entry
points a user calls:

  1. generate, from ``--seed``, a Graph500-shaped Kronecker graph
     (``gen_kron(22, 16)``: A/B/C = 0.57/0.19/0.19, 4,194,304 vertices,
     67,108,864 edges) and a GAP-urand-shaped uniform graph
     (``gen_uniform(2**20, 16)``);
  2. register both with ``GraphFrontend`` (``PreprocessPipeline``:
     degree count, degree-sort relabel, PB CSR rebuild, slack layout);
  3. ``warmup()`` (pre-decided reduce keys + probe queries);
  4. two tenants submit bfs / sssp / ppr / pagerank / kcore queries on
     both graphs; the frontend drains them;
  5. every answer is checked against a plain host oracle: scipy
     ``csgraph`` BFS and Dijkstra, numpy k-core peeling and numpy power
     iteration. The rebuilt CSR itself is checked against the generated
     edge list first.

It prints the devices, each phase's time, the executor's reduce
decisions by method and by fused realization (``pallas`` = the compiled
C-Buffer kernel, ``jnp`` = the XLA scatter), the seconds the traversal
layer spends building level streams on the host (the executor's
``host_streams``), and peak device memory.

``--chips 4`` runs only the sharded path on a 4-device stream mesh:
``shard_build_csr`` and ``pagerank_sharded`` on the same Kronecker graph,
compared against the one-device CSR (exactly) and PageRank (allclose)
computed in the same process.

The last line of standard output is one JSON object naming the device.
Without a TPU the script exits non-zero before running anything.

  python chip_smoke.py
  python chip_smoke.py --chips 4
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
READ_KINDS = ("bfs", "sssp", "ppr", "pagerank", "kcore")
TENANTS = ("tenant-a", "tenant-b")
EDGEFACTOR = 16  # Graph500 / GAP edges per vertex
ITERS = 10  # ppr / pagerank power iterations
K_CORE = 3
PR_RTOL, PR_ATOL = 2e-3, 1e-9  # float32 device sums vs float64 oracle
SSSP_RTOL = 1e-5


class Phases:
    """Wall-clock time of each named phase, printed as it ends."""

    def __init__(self):
        self.seconds: dict = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        yield
        self.seconds[name] = time.perf_counter() - t0
        print(f"[phase] {name}: {self.seconds[name]:.2f} s", flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    # smaller scales are for rehearsing the script on a CPU
    ap.add_argument("--scale", type=int, default=22, help="Kronecker scale")
    ap.add_argument("--urand-scale", type=int, default=20, help="log2 vertices of the uniform graph")
    return ap.parse_args(argv)


# ---------------------------------------------------------------------------
# Host oracles (numpy / scipy, float64) on the generated edge list.
# ---------------------------------------------------------------------------


def edges_of(registered):
    """(src, dst, weight) of a registered graph's rebuilt CSR, in the
    ORIGINAL vertex ids (the frontend relabels internally)."""
    csr = registered.csr
    off = np.asarray(csr.offsets).astype(np.int64)
    nei = np.asarray(csr.neighs)
    old = np.empty(csr.num_nodes, np.int64)
    old[np.asarray(registered.new_ids)] = np.arange(csr.num_nodes)
    src = old[np.repeat(np.arange(csr.num_nodes), np.diff(off))]
    return src, old[nei], np.asarray(registered.weights).astype(np.float64)


class Oracle:
    """A registered graph's edges in ORIGINAL ids, checked against the
    generated edge list, as scipy matrices: the lightest edge per
    (src, dst) pair for BFS and Dijkstra, and edge multiplicities for
    PageRank (scipy would sum parallel edges' weights)."""

    def __init__(self, registered, coo):
        import scipy.sparse as sp

        n = self.n = coo.num_nodes
        src, dst, w = edges_of(registered)
        key = src * n + dst
        order = np.argsort(key, kind="stable")
        key = key[order]
        want = np.sort(np.asarray(coo.src).astype(np.int64) * n + np.asarray(coo.dst))
        if not np.array_equal(key, want):
            raise AssertionError("rebuilt CSR edges differ from the generated edge list")
        starts = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
        pair = key[starts]
        indptr = np.searchsorted(pair // n, np.arange(n + 1))
        cols = pair % n
        lightest = np.minimum.reduceat(w[order], starts)
        multiplicity = np.diff(np.r_[starts, key.size]).astype(np.float64)
        self.matrix = sp.csr_matrix((lightest, cols, indptr), shape=(n, n))
        self.counts_t = sp.csr_matrix((multiplicity, cols, indptr), shape=(n, n)).T.tocsr()
        self.outdeg = np.bincount(src, minlength=n)
        self.src, self.dst = src, dst


def bfs_levels(matrix, source: int) -> np.ndarray:
    """BFS levels from scipy's BFS tree: a vertex's level is its
    predecessor's plus one (INT32_MAX where unreached)."""
    from scipy.sparse.csgraph import breadth_first_order

    order, pred = breadth_first_order(matrix, source, directed=True)
    big = np.iinfo(np.int32).max
    lvl = np.full(matrix.shape[0], big, np.int64)
    lvl[source] = 0
    pending = order[1:]
    while pending.size:
        ready = lvl[pred[pending]] < big
        lvl[pending[ready]] = lvl[pred[pending[ready]]] + 1
        pending = pending[~ready]
    return lvl


def pagerank_oracle(oracle, source, iters, damp=0.85) -> np.ndarray:
    """Power iteration with the engine's semantics: out-degree clamped
    to 1 (dangling mass dropped), restart at ``source`` or uniform."""
    n = oracle.n
    outdeg = np.maximum(oracle.outdeg, 1).astype(np.float64)
    if source is None:
        restart = np.full(n, 1.0 / n)
    else:
        restart = np.zeros(n)
        restart[source] = 1.0
    ranks = restart.copy()
    for _ in range(iters):
        incoming = oracle.counts_t @ (ranks / outdeg)
        ranks = (1.0 - damp) * restart + damp * incoming
    return ranks


def kcore_oracle(oracle, k) -> np.ndarray:
    """Out-degree k-core by peeling: removing a vertex deletes its
    out-edges, decrementing their targets."""
    n, src, dst = oracle.n, oracle.src, oracle.dst
    deg = oracle.outdeg
    alive = np.ones(n, bool)
    frontier = deg < k
    while frontier.any():
        alive &= ~frontier
        deg = deg - np.bincount(dst[frontier[src]], minlength=n)
        frontier = alive & (deg < k)
    return alive


def check_answer(q, oracle: Oracle) -> None:
    got = np.asarray(q.result)
    if q.kind == "bfs":
        want = bfs_levels(oracle.matrix, q.source)
        ok = np.array_equal(got.astype(np.int64), want)
    elif q.kind == "sssp":
        from scipy.sparse.csgraph import dijkstra

        want = dijkstra(oracle.matrix, directed=True, indices=q.source)
        reached = np.isfinite(want)
        big = np.finfo(np.float32).max
        ok = bool(
            np.array_equal(got < big, reached)
            and np.allclose(got[reached], want[reached], rtol=SSSP_RTOL)
        )
    elif q.kind in ("ppr", "pagerank"):
        want = pagerank_oracle(oracle, q.source if q.kind == "ppr" else None, q.iters)
        ok = np.allclose(got, want, rtol=PR_RTOL, atol=PR_ATOL)
    else:  # kcore
        want = kcore_oracle(oracle, q.k)
        ok = np.array_equal(got.astype(bool), want)
    if not ok:
        raise AssertionError(f"{q.kind} query {q.qid} on {q.graph} disagrees with the host oracle")


# ---------------------------------------------------------------------------
# One chip: the serving path end to end.
# ---------------------------------------------------------------------------


def decision_counts(entries) -> dict:
    """Reduce/update decisions by method, and fused ones by realization."""
    red = [e for e in entries if e.get("kind") in ("reduce", "update")]
    return {
        "decisions": len(red),
        "by_method": dict(collections.Counter(e["method"] for e in red)),
        "fused_by_realization": dict(
            collections.Counter(e.get("realization", "unnamed") for e in red if e["method"] == "fused")
        ),
    }


def run_one_chip(args, phase: Phases) -> None:
    import jax

    from repro.core.executor import PBExecutor
    from repro.core.graph import gen_kron, gen_uniform
    from repro.serving.graph_frontend import GraphFrontend, GraphQuery

    with phase("generate"):
        graphs = {
            f"kron{args.scale}": gen_kron(args.scale, EDGEFACTOR, seed=args.seed),
            f"urand{args.urand_scale}": gen_uniform(
                1 << args.urand_scale, EDGEFACTOR, seed=args.seed + 1
            ),
        }
        jax.block_until_ready([(g.src, g.dst) for g in graphs.values()])
    for name, g in graphs.items():
        print(f"[graph] {name}: n={g.num_nodes} m={g.num_edges}")

    ex = PBExecutor()
    print(f"[executor] hardware model {ex.hw.name}, VMEM budget {ex.hw.fast_levels[-1]} B")
    fe = GraphFrontend(executor=ex, max_batch=2)
    logs = {name: [] for name in ("preprocess", "warmup", "queries")}

    @contextlib.contextmanager
    def logged(name):
        """Collect the phase's decisions, and print the traversal layer's
        host share: frontier expansion and stream building in host numpy,
        uploads included."""
        ex.add_decision_sink(logs[name])
        ex.host_streams.reset()
        try:
            yield
        finally:
            ex.remove_decision_sink(logs[name])
            hs = ex.host_streams
            print(
                f"[host-streams] {name}: {hs.seconds:.3f} s over {hs.levels} "
                f"levels, {hs.upload_bytes} B uploaded"
            )

    registered = {}
    with phase("preprocess"), logged("preprocess"):
        for name, coo in graphs.items():
            registered[name] = fe.register_graph(name, coo, seed=args.seed)
            stages = ", ".join(
                f"{s.name} {s.seconds:.3f}s" for s in registered[name].report.stages
            )
            print(f"[preprocess] {name}: {stages}")

    with phase("warmup"), logged("warmup"):
        wr = fe.warmup()
    print(f"[warmup] {wr.decisions} decisions, {wr.probes} probe queries")

    rng = np.random.default_rng(args.seed)
    queries = []
    for name, coo in graphs.items():
        has_out = np.flatnonzero(np.bincount(np.asarray(coo.src), minlength=coo.num_nodes))
        for kind in READ_KINDS:
            for tenant in TENANTS:
                queries.append(GraphQuery(
                    tenant=tenant, graph=name, kind=kind,
                    source=int(rng.choice(has_out)), iters=ITERS, k=K_CORE,
                ))
    with phase("queries"), logged("queries"):
        for q in queries:
            fe.submit(q)
        done = fe.run_until_drained()
    if len(done) != len(queries):
        raise AssertionError(f"{len(done)} of {len(queries)} queries completed")
    print(f"[queries] {len(done)} queries in {fe.ticks} ticks")

    stats = jax.devices()[0].memory_stats() or {}
    print(f"[memory] peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    for name, entries in logs.items():
        print(f"[decisions] {name}: {json.dumps(decision_counts(entries), sort_keys=True)}")

    with phase("oracle"):
        for name, coo in graphs.items():
            oracle = Oracle(registered[name], coo)
            for q in done:
                if q.graph == name:
                    check_answer(q, oracle)
    print(f"[oracle] {len(done)} answers agree with the host oracle")
    ran = decision_counts(logs["preprocess"] + logs["queries"])
    if not ran["fused_by_realization"].get("pallas"):
        raise AssertionError("no reduce ran on the compiled Pallas fused kernel")
    if ran["fused_by_realization"].get("unnamed"):
        raise AssertionError("a fused decision does not name its realization")


# ---------------------------------------------------------------------------
# Four chips: the sharded CSR build and PageRank vs one device.
# ---------------------------------------------------------------------------


def run_sharded(args, phase: Phases) -> None:
    import jax

    from repro.core.distributed_pb import make_stream_mesh, shard_build_csr
    from repro.core.executor import get_default_executor
    from repro.core.graph import gen_kron
    from repro.core.pagerank import pagerank_sharded

    mesh = make_stream_mesh(4)
    ex = get_default_executor()
    log: list = []
    ex.add_decision_sink(log)
    with phase("generate"):
        coo = gen_kron(args.scale, EDGEFACTOR, seed=args.seed)
        jax.block_until_ready((coo.src, coo.dst))
    print(f"[graph] kron{args.scale}: n={coo.num_nodes} m={coo.num_edges}")
    with phase("one-device"):
        csr1 = shard_build_csr(coo, mesh=None)
        pr1 = pagerank_sharded(coo, mesh=None, iters=ITERS).ranks
        jax.block_until_ready((csr1, pr1))
    n_single = len(log)
    with phase("sharded"):
        csr4 = shard_build_csr(coo, mesh=mesh)
        pr4 = pagerank_sharded(coo, mesh=mesh, iters=ITERS).ranks
        jax.block_until_ready((csr4, pr4))
    for e in log[n_single:]:
        if "mesh" in e or e.get("kind") == "shard_exchange":
            facts = {k: e.get(k) for k in ("kind", "method", "realization", "capacity", "overflow", "pipeline_chunks")}
            print(f"[sharded] decision {json.dumps(facts, sort_keys=True)}")
    holders = {s.device for s in pr4.addressable_shards}
    print(f"[sharded] ranks sharding {pr4.sharding}, held on {len(holders)} devices")
    if len(holders) != 4:
        raise AssertionError(f"sharded ranks live on {len(holders)} device(s), want 4")
    # each device held its quarter of the (src, dst) stream at least
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in mesh.devices.flat]
    print(f"[sharded] peak_bytes_in_use per device {peaks}")
    if None not in peaks and min(peaks) < 2 * coo.num_edges:
        raise AssertionError("a mesh device never held its edge shard")
    for field in ("offsets", "neighs"):
        if not np.array_equal(np.asarray(getattr(csr1, field)), np.asarray(getattr(csr4, field))):
            raise AssertionError(f"sharded CSR {field} differ from the one-device build")
    a, b = np.asarray(pr1), np.asarray(pr4)
    print(f"[sharded] pagerank max |diff| {float(np.max(np.abs(a - b))):.3e}, max rank {float(a.max()):.3e}")
    if not np.allclose(b, a, rtol=1e-4, atol=1e-10):
        raise AssertionError("sharded PageRank is not allclose to the one-device result")
    print("[sharded] CSR identical, PageRank allclose")


def main(argv=None) -> int:
    args = parse_args(argv)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} device(s)", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"[devices] {devices}")
    print(f"[compile-cache] {enable_compile_cache()}")
    phase = Phases()
    (run_sharded if args.chips == 4 else run_one_chip)(args, phase)
    print(f"[phases] {json.dumps({k: round(v, 3) for k, v in phase.seconds.items()})}")
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
