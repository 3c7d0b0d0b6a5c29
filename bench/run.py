#!/usr/bin/env python3
"""Benchmark of the PB graph engine on the chip: one cell, one run.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (a graph,
``bench/configs/<name>.json``) and a traffic mix (``bench/traffic/<name>.json``),
whose ``job`` names a job kind (``bench/jobs/<kind>.py``). A run:

  1. keeps JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``,
     else at ``<checkout>/.jax_cache``;
  2. fails (exit 2, no result) unless JAX finds a TPU with the cell's chips;
  3. generates the graph's arcs on the cell's chips from ``--seed`` (``bench/gen.py``);
  4. runs one whole job to warm up every program the window calls;
  5. runs whole jobs, one after another, until ``--seconds`` have passed;
  6. compares what the jobs produced with the plain reference
     (``bench/reference/``), once the window has closed, peak memory has
     been read and the program's state is freed;
  7. prints one JSON line: the cell's end-to-end metrics with ``--trace 0``;
     with ``--trace 1`` its per-layer metrics (``bench/metrics/<name>.py``),
     read from a profiler trace of the window's first job and from the
     program's stage reports.

``setup_s`` runs from the start of this script to the first timed job.
Each number compared is printed beside its limit as the last lines of
standard error, and under ``checks``, the last key of the result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_START:8.2f}s] {msg}", file=sys.stderr, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True, help="a cell name of BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True, help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


# ---------------------------------------------------------------------------
# The benchmark's files, found by the names in BENCHMARK.json.
# ---------------------------------------------------------------------------


def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_files(spec: dict, cell_name: str, root: str = ROOT):
    """(cell, configuration, traffic) of one cell, from their files."""
    cells = {c["name"]: c for c in spec["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no cell {cell_name!r} in BENCHMARK.json (have {sorted(cells)})")
    cell = cells[cell_name]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return cell, config, traffic


def job_module(kind: str):
    return importlib.import_module(f"bench.jobs.{kind}")


def metric_reader(name: str, root: str = ROOT):
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench.metrics.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reports(metric: dict, cell_name: str) -> bool:
    """Whether a cell reports ``metric``: the cells its ``workloads``
    lists, every cell without the key."""
    return cell_name in metric.get("workloads", [cell_name])


def rate_metric(spec: dict, cell_name: str) -> dict:
    """The end-to-end metric other than ``setup_s`` that the cell reports:
    work done in the window over its wall time."""
    return next(m for m in spec["end_to_end"] if m["name"] != "setup_s" and reports(m, cell_name))


# ---------------------------------------------------------------------------
# Device, compile cache, trace session.
# ---------------------------------------------------------------------------


def enable_compile_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def tpu_devices(chips: int) -> list:
    """The first ``chips`` TPU devices; exits 2 (no result) without them."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"no TPU: JAX found {devices[0].platform}")
        sys.exit(2)
    if len(devices) < chips:
        log(f"the cell needs {chips} chips, JAX found {len(devices)}")
        sys.exit(2)
    return devices[:chips]


@contextlib.contextmanager
def compiles_seen():
    """The backend compiles made while the block runs, as a list of their
    seconds: none may fall in the window."""
    import jax

    seen: list = []

    def on_event(event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            seen.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


@contextlib.contextmanager
def traced(log_dir: str):
    """A profiler session around the block, with the Python tracer on so
    that idle gaps can be named by the host calls that left them."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.traced"):
            yield
    finally:
        jax.profiler.stop_trace()


def device_info(devices) -> dict:
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind, "count": len(devices),
            "memory_peak_bytes": peak}


# ---------------------------------------------------------------------------
# One run of one cell.
# ---------------------------------------------------------------------------


def run_cell(spec: dict, cell_name: str, seed: int, seconds: float, trace: bool,
             devices, config: dict = None, t_start: float = T_START) -> dict:
    """Set up, warm, measure, check; the result line as a dict. ``config``
    replaces the cell's configuration (a small graph, in tests)."""
    import jax
    import numpy as np

    from bench import gen

    cell, cfg, traffic = cell_files(spec, cell_name)
    config = config or cfg
    jobs_mod = job_module(traffic["job"])
    rate = rate_metric(spec, cell_name)
    with jax.profiler.TraceAnnotation("bench.generate"):
        coo = gen.generate(config, gen.seed_key(seed), devices)
        jax.block_until_ready((coo.src, coo.dst))
    log(f"{cell_name}: {config['name']} n={coo.num_nodes} m={coo.num_edges} generated")
    job = jobs_mod.Job(coo, traffic, np.random.default_rng(seed))
    with jax.profiler.TraceAnnotation("bench.warm"):
        job.run()
    from repro.core.executor import get_default_executor

    for d in get_default_executor().decision_log:
        log(f"decision {json.dumps(d, sort_keys=True)}")
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s")

    jobs, window_s, readings, summary = measure(job, seconds, trace)

    device = device_info(devices)
    counts = job.counts()
    src, dst = np.asarray(coo.src), np.asarray(coo.dst)
    del coo
    kept = job.fetch()
    with jax.profiler.TraceAnnotation("bench.check"):
        numbers, failed = jobs_mod.check(kept, src, dst, config, traffic)
    correct = failed == 0 and all(v <= lim for v, lim in numbers.values())

    metrics = {}
    if trace:
        ctx = {
            "cell": cell, "config": config, "traffic": traffic, "counts": counts,
            "traced_jobs": 1, "trace": summary, "readings": readings,
            "device_kind": device["kind"],
        }
        for m in spec["per_layer"]:
            if reports(m, cell_name):
                value = metric_reader(m["name"])(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
    else:
        metrics[rate["name"]] = {"value": jobs * job.work / window_s, "unit": rate["unit"]}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    result = {"correct": bool(correct), "attempted": jobs, "failed": int(failed),
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": summary.top_ops(), "idle_gaps": summary.top_gaps()}
    result["checks"] = {k: {"value": finite(v), "limit": lim} for k, (v, lim) in numbers.items()}
    return result


def measure(job, seconds: float, trace: bool):
    """Whole jobs back to back until ``seconds`` have passed: (jobs, their
    wall seconds, each job's readings, the trace summary of the first job
    when ``trace``)."""
    import jax

    from bench import trace as trace_mod

    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    readings, jobs, summary = [], 0, None
    try:
        with compiles_seen() as compiled:
            t0 = time.perf_counter()
            while True:
                session = traced(log_dir) if trace and jobs == 0 else contextlib.nullcontext()
                with session, jax.profiler.TraceAnnotation("bench.job"):
                    out = job.run()
                readings.append(job.readings(out))
                job.keep(out)
                del out
                jobs += 1
                if time.perf_counter() - t0 >= seconds:
                    break
            window_s = time.perf_counter() - t0
        if trace:
            summary = trace_mod.reduce(trace_mod.find_xplane(log_dir))
    finally:
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)
    log(f"window {window_s:.3f} s, {jobs} jobs, {len(compiled)} compiles in the window")
    return jobs, window_s, readings, summary


def finite(v):
    """A JSON-safe number: an infinite or undefined reading as a string."""
    return v if math.isfinite(v) else str(v)


def main(argv=None) -> int:
    args = parse_args(argv)
    spec = load_spec()
    cell, _, _ = cell_files(spec, args.workload)
    cache = enable_compile_cache()
    devices = tpu_devices(int(cell["chips"]))
    log(f"devices {devices}, compile cache {cache}")
    result = run_cell(spec, args.workload, args.seed, args.seconds, bool(args.trace), devices)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def script_path() -> None:
    """Imports for a script under ``bench/``: the checkout root for
    ``bench.*`` and ``src`` for the program, and never ``bench/`` itself,
    whose module names would shadow others."""
    sys.path[:] = [ROOT, os.path.join(ROOT, "src")] + [
        p for p in sys.path if os.path.abspath(p or ".") != HERE
    ]


if __name__ == "__main__":
    script_path()
    sys.exit(main())
