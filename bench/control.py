#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, in one process.

  python3 bench/control.py --workload <cell> --program-seeds 1,2,3 --control-seeds 4,5,6

For each program seed: the cell's graph from that seed, one job through
the timed path, and the numbers its check compares. For each control
seed: the same numbers for the control, the plain reference computed in
the precision below the one the configuration states (or, for exact
results, with one guarantee of the configuration broken), put in the
program's place. A limit lies above every program reading and below
every control reading (PERF.md, "Correctness limits"). The benchmark's
own runs never run this. One JSON line per reading on standard output.
"""
import json
import os
import sys


def readings(cell_name: str, program_seeds, control_seeds, config=None, devices=None):
    """Yield ``(kind, seed, numbers)`` for every seed, the graph made on
    ``devices`` (the cell's chips); ``config`` replaces the cell's
    configuration (a small graph, in tests)."""
    import numpy as np

    from bench import gen, run

    spec = run.load_spec()
    _, cfg, traffic = run.cell_files(spec, cell_name)
    config = config or cfg
    jobs_mod = run.job_module(traffic["job"])
    for kind, seeds in (("program", program_seeds), ("control", control_seeds)):
        for seed in seeds:
            coo = gen.generate(config, gen.seed_key(seed), devices)
            src, dst = np.asarray(coo.src), np.asarray(coo.dst)
            if kind == "program":
                job = jobs_mod.Job(coo, traffic, np.random.default_rng(seed))
                job.keep(job.run())
                del coo
                numbers, _ = jobs_mod.check(job.fetch(), src, dst, config, traffic)
            else:
                numbers = jobs_mod.control(coo.src, coo.dst, src, dst, config, traffic)
                del coo
            yield kind, seed, {k: v for k, (v, _) in numbers.items()}


def main(argv=None) -> int:
    import argparse

    from bench import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)

    def seeds(s):
        return [int(x) for x in s.split(",") if x]

    spec = run.load_spec()
    cell, _, _ = run.cell_files(spec, args.workload)
    run.enable_compile_cache()
    devices = run.tpu_devices(int(cell["chips"]))
    for kind, seed, numbers in readings(
        args.workload, seeds(args.program_seeds), seeds(args.control_seeds), devices=devices
    ):
        print(json.dumps({"cell": args.workload, "kind": kind, "seed": seed, "numbers": numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
    from bench import run

    run.script_path()
    sys.exit(main())
