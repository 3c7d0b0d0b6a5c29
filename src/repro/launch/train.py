"""Training launcher.

Single-host examples / tests:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b \
      --preset smoke --steps 50 --mesh host:2x2

On a real multi-host pod each host runs this same entrypoint after
``jax.distributed.initialize`` (coordinator address, process count and
id passed explicitly); the mesh spec 'prod' / 'prod-multipod' then
spans all processes.

Features wired in: deterministic restartable data pipeline, async
checkpointing + auto-resume, straggler detection, heartbeat watchdog,
optional int8 error-feedback gradient compression, elastic re-mesh on
restart (the checkpoint re-places onto whatever mesh is available).
"""
from __future__ import annotations

import argparse
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs.registry import SHAPES, ShapeSpec, get_config
from repro.data.pipeline import make_data
from repro.distributed import sharding as shd
from repro.ft.resilience import Heartbeat, StragglerDetector
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import params as pp
from repro.models import transformer as T
from repro.serving.graph_frontend import Clock
from repro.train import steps as steps_mod
from repro.train.optimizer import init_opt_state
from repro.train.steps import TrainState, default_opt_config


def parse_mesh(spec: str):
    if spec == "prod":
        return make_production_mesh()
    if spec == "prod-multipod":
        return make_production_mesh(multi_pod=True)
    if spec.startswith("host:"):
        d, m = spec.split(":")[1].split("x")
        return make_host_mesh(int(d), int(m))
    if spec == "none":
        return None
    raise ValueError(spec)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--preset", choices=["full", "smoke"], default="smoke",
                    help="smoke: reduced config of the same family (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--mesh", default="none", help="none|host:DxM|prod|prod-multipod")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true", default=True)
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient accumulation (microbatching) factor")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.preset == "smoke":
        cfg = cfg.reduced()
    shape = SHAPES.get(args.shape)
    if shape is None or args.preset == "smoke":
        shape = ShapeSpec("custom", args.seq_len or 128, args.batch or 8, "train")
    if args.seq_len or args.batch:
        shape = dataclasses.replace(
            shape, seq_len=args.seq_len or shape.seq_len,
            global_batch=args.batch or shape.global_batch,
        )

    mesh = parse_mesh(args.mesh)
    oc = default_opt_config(cfg, total_steps=args.steps)
    train_step = steps_mod.make_train_step(cfg, oc, accum_steps=args.accum)
    data = make_data(cfg, shape, host_index=jax.process_index(),
                     host_count=jax.process_count())

    def build_state():
        boxed = T.init_params(jax.random.PRNGKey(0), cfg)
        params, _ = pp.unbox(boxed)
        return TrainState(params, init_opt_state(params, oc))

    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start_step = 0

    ctx = shd.use_mesh(mesh) if mesh is not None else _nullctx()
    with ctx:
        state = build_state()
        if ckpt and args.resume:
            restored, at = ckpt.restore(state)
            if restored is not None:
                state, start_step = restored, at
                print(f"[train] resumed from step {at}")
        # resume goes through the checkpoint manager, never a dead state:
        # donate-ok: the old state is unreferenced once jstep returns
        jstep = jax.jit(train_step, donate_argnums=(0,))
        hb = Heartbeat(timeout_s=600, on_timeout=lambda: print("[ft] WATCHDOG FIRED")).start()
        sd = StragglerDetector()
        clock = Clock()  # monotonic: step dt survives NTP wall-clock steps
        t_last = clock.now()
        for step in range(start_step, args.steps):
            batch = {k: jnp.asarray(v) for k, v in data.batch_at(step).items()}
            state, metrics = jstep(state, batch)
            hb.beat()
            if (step + 1) % args.log_every == 0 or step == start_step:
                loss = float(metrics["loss"])
                dt = clock.now() - t_last
                t_last = clock.now()
                slow = sd.observe(f"host{jax.process_index()}", dt)
                tok_s = shape.global_batch * shape.seq_len * args.log_every / max(dt, 1e-9)
                print(f"[train] step={step+1} loss={loss:.4f} "
                      f"{tok_s:,.0f} tok/s{' STRAGGLER' if slow else ''}", flush=True)
            if ckpt and (step + 1) % args.ckpt_every == 0:
                ckpt.save(step + 1, state)  # async
        if ckpt:
            ckpt.save(args.steps, state, blocking=True)
        hb.stop()
        print(f"[train] done: {args.steps} steps, final loss {float(metrics['loss']):.4f}")
        return float(metrics["loss"])


class _nullctx:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
