"""Serving launcher: continuous-batching engine over a mesh.

Single-host smoke:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --requests 8

Production pods run the same entrypoint with --mesh prod after
``jax.distributed.initialize``; decode caches shard per the seq_kv/batch
rules (see distributed/sharding.py).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.distributed import sharding as shd
from repro.launch.train import parse_mesh, _nullctx
from repro.models import transformer as T
from repro.models.params import unbox
from repro.serving.server import Engine, Request


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--preset", choices=["full", "smoke"], default="smoke")
    ap.add_argument("--mesh", default="none")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.preset == "smoke":
        cfg = cfg.reduced()
    mesh = parse_mesh(args.mesh)
    ctx = shd.use_mesh(mesh) if mesh is not None else _nullctx()
    with ctx:
        params, _ = unbox(T.init_params(jax.random.PRNGKey(0), cfg))
        eng = Engine(cfg, params, slots=args.slots, max_len=args.max_len)
        rng = np.random.default_rng(0)
        t0 = time.perf_counter()
        for rid in range(args.requests):
            plen = int(rng.integers(8, args.max_len // 4))
            prompt = rng.integers(0, cfg.vocab_size, size=plen).astype(np.int32)
            eng.submit(Request(rid=rid, prompt=prompt, max_new=args.max_new))
        done = eng.run_until_drained()
        dt = time.perf_counter() - t0
        tok = sum(len(r.out) for r in done)
        print(f"[serve] {len(done)} requests, {tok} tokens, {tok/max(dt,1e-9):.1f} tok/s")
        return len(done)


if __name__ == "__main__":
    main()
