"""Benchmark harness: one module per paper table/figure + beyond-paper
integration benches. Prints ``name,us_per_call,derived`` CSV.

BENCH_SCALE=small (default, CI-sized) | full (EXPERIMENTS.md numbers).
``--smoke`` runs a fast subset (1 rep, 1 warmup, small scale) — the
benchmark leg of scripts/verify.sh — and writes ``BENCH_smoke.json``
(rows + every PBExecutor method decision) at the repo root so each PR
leaves a perf trajectory the next one can diff against.
"""
from __future__ import annotations

import json
import os
import sys
import time


MODULES = [
    "benchmarks.table1_pb_speedup",
    "benchmarks.table2_pb_ideal",
    "benchmarks.fig2_preproc_cost",
    "benchmarks.fig3_binrange",
    "benchmarks.fig5_end2end",
    "benchmarks.fig6_breakdown",
    "benchmarks.fig7_scaling",
    "benchmarks.fig8_traversal",
    "benchmarks.fig9_spmm",
    "benchmarks.fig10_updates",
    "benchmarks.serving_load",
    "benchmarks.moe_dispatch",
    "benchmarks.embed_grad",
    "benchmarks.executor_autotune",
]

# Fast, representative subset: one paper table, the preprocessing
# pipeline + amortization sweep, the executor's own selection bench, one
# framework-integration stream, and the sharded scaling sweep (it forces
# its own 8-device subprocess, so it runs anywhere).
SMOKE_MODULES = [
    "benchmarks.table1_pb_speedup",
    "benchmarks.fig2_preproc_cost",
    "benchmarks.fig6_breakdown",
    "benchmarks.fig7_scaling",
    "benchmarks.fig8_traversal",
    "benchmarks.fig9_spmm",
    "benchmarks.fig10_updates",
    "benchmarks.serving_load",
    "benchmarks.executor_autotune",
    "benchmarks.moe_dispatch",
]


def _write_smoke_json(all_rows, module_secs) -> None:
    """BENCH_smoke.json: timings + the executor's method decisions, the
    perf trajectory future PRs diff against (ISSUE 2 CI/tooling)."""
    import jax

    from repro.core import get_default_executor

    parsed = []
    for row in all_rows:
        name, us, derived = row.split(",", 2)
        parsed.append({"name": name, "us_per_call": float(us), "derived": derived})
    # Device topology makes bench trajectories comparable across PRs: a
    # timing measured on 1 CPU device is not evidence about an 8-device
    # mesh (the same reason PBExecutor._key carries the topology).
    blob = {
        "version": 1,
        "scale": os.environ.get("BENCH_SCALE", "small"),
        "backend": jax.default_backend(),
        "topology": {
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
            "local_device_count": jax.local_device_count(),
            "device_kind": jax.devices()[0].device_kind,
            "stream_mesh_shape": {"shard": jax.device_count()},
            "xla_flags": os.environ.get("XLA_FLAGS", ""),
        },
        "rows": parsed,
        "decisions": get_default_executor().decision_log,
        "module_seconds": module_secs,
    }
    out = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "BENCH_smoke.json")
    with open(out, "w") as f:
        json.dump(blob, f, indent=1)
    print(f"# wrote {out}", file=sys.stderr)


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    smoke = "--smoke" in sys.argv[1:]
    modules = MODULES
    if smoke:
        os.environ["BENCH_SCALE"] = "small"
        os.environ.setdefault("REPRO_BENCH_REPS", "1")
        os.environ.setdefault("REPRO_BENCH_WARMUP", "1")
        modules = SMOKE_MODULES
    print("name,us_per_call,derived")
    failures = 0
    all_rows = []
    module_secs = {}
    for modname in modules:
        t0 = time.perf_counter()
        try:
            mod = __import__(modname, fromlist=["run"])
            for row in mod.run().emit():
                all_rows.append(row)
                print(row, flush=True)
            module_secs[modname] = round(time.perf_counter() - t0, 1)
            print(
                f"# {modname} done in {time.perf_counter()-t0:.0f}s",
                file=sys.stderr,
            )
        except Exception as e:  # keep the harness going; report at the end
            failures += 1
            row = f"{modname},0.0,ERROR:{type(e).__name__}:{e}"
            all_rows.append(row)  # recorded in BENCH_smoke.json so the
            print(row, flush=True)  # row guard also sees module crashes
    if smoke:
        _write_smoke_json(all_rows, module_secs)
    if failures:
        raise SystemExit(f"{failures} benchmark module(s) failed")


if __name__ == "__main__":
    main()
