"""Training launcher: --arch <id> [--smoke] on the host mesh, with
checkpoint/restart fault tolerance, preemption handling (SIGTERM ->
final checkpoint -> clean exit), straggler detection (slow-step log),
and optional DDP + int8 gradient compression.

This driver is the single-host harness.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-14b --smoke \
      --steps 100 --ckpt /tmp/ck [--ddp --compress]
"""
from __future__ import annotations

import argparse
import signal
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

from repro.checkpoint.manager import CheckpointManager
from repro.configs.registry import ARCH_IDS, get_config
from repro.data.lm_data import LMDataConfig, batches
from repro.train.optimizer import OptConfig
from repro.train.train_step import (init_ddp_state, init_train_state,
                                    make_ddp_train_step, make_train_step)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(ARCH_IDS))
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--ddp", action="store_true",
                    help="shard_map DDP over host devices")
    ap.add_argument("--compress", action="store_true",
                    help="int8 gradient compression (with --ddp)")
    ap.add_argument("--straggler-factor", type=float, default=3.0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, smoke=True)
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.2f}M "
          f"devices={len(jax.devices())}")
    opt = OptConfig(lr=args.lr, warmup_steps=10, total_steps=args.steps)

    if args.ddp:
        mesh = jax.make_mesh((len(jax.devices()),), ("data",),
                             axis_types=(AxisType.Auto,))
        state = init_ddp_state(cfg, jax.random.PRNGKey(0))
        step_fn = jax.jit(make_ddp_train_step(cfg, opt, mesh,
                                              compress=args.compress))
        mesh_ctx = jax.set_mesh(mesh)
        mesh_ctx.__enter__()
    else:
        state = init_train_state(cfg, jax.random.PRNGKey(0))
        step_fn = jax.jit(make_train_step(cfg, opt))  # no donation: m/v
        # share XLA zero constants on host; donating would alias twice

    mgr = CheckpointManager(args.ckpt) if args.ckpt else None
    start = 0
    if mgr is not None and mgr.latest_step() is not None:
        start = mgr.latest_step()
        target = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
        state = mgr.restore(start, target)
        print(f"resumed from step {start}")

    stop = {"now": False}

    def _sigterm(signum, frame):   # preemption: checkpoint + exit
        print("SIGTERM: writing final checkpoint", flush=True)
        stop["now"] = True
    signal.signal(signal.SIGTERM, _sigterm)

    data = batches(LMDataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                batch=args.batch))
    step_times = []
    for step in range(start, args.steps):
        b = next(data)
        batch = {"tokens": jnp.asarray(b["tokens"]),
                 "labels": jnp.asarray(b["labels"])}
        if cfg.mrope:
            B, S = batch["tokens"].shape
            batch["positions"] = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32)[None, :, None], (B, S, 3))
        if cfg.encoder_layers:
            batch["enc_input"] = jnp.zeros(
                (args.batch, cfg.encoder_ctx, cfg.d_model), jnp.float32)
        t0 = time.time()
        state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])
        dt = time.time() - t0
        step_times.append(dt)
        med = float(np.median(step_times[-20:]))
        if len(step_times) > 5 and dt > args.straggler_factor * med:
            print(f"[straggler] step {step}: {dt:.2f}s vs median "
                  f"{med:.2f}s -- at pod scale this triggers re-slicing",
                  flush=True)
        if (step + 1) % 10 == 0:
            print(f"step {step+1:4d} loss {loss:.3f} "
                  f"({args.batch*args.seq/dt:,.0f} tok/s)", flush=True)
        if mgr is not None and ((step + 1) % args.ckpt_every == 0
                                or stop["now"]):
            mgr.save_async(step + 1, state)
        if stop["now"]:
            mgr and mgr.wait()
            return 0
    mgr and mgr.wait()
    print("done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
