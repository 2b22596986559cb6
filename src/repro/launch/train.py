"""Training launcher with checkpoint/auto-resume fault tolerance.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
        --steps 50 --ckpt-dir /tmp/ckpt --auto-resume

Production shape: the same entry point runs under ``runtime.ft.supervise``
(restart-on-failure); ``--auto-resume`` restores the latest COMMITted
checkpoint (params, optimizer, data-pipeline cursor) so a SIGKILL at any
point loses at most ``--ckpt-every`` steps.  Demonstrated by
tests/test_fault_tolerance.py and examples/train_small.py.
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def build(args):
    import jax
    from repro.configs import get_config, reduced
    from repro.configs.base import ShapeConfig
    from repro.core import steps
    from repro.core.partition import ShardingPlan
    from repro.data import DataConfig, PackedBatches
    from repro.launch.mesh import host_mesh
    from repro.optim import AdamWConfig, cosine_schedule

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    n_dev = len(jax.devices())
    tp = args.tp or (1 if args.smoke else min(16, n_dev))
    dp = max(1, n_dev // tp) if args.dp == 0 else args.dp
    mesh = host_mesh(tp=tp, dp=dp)
    plan = ShardingPlan(tp=tp, remat=args.remat)
    shape = ShapeConfig("cli", "train", args.seq_len, args.batch)
    opt = AdamWConfig(lr=args.lr,
                      schedule=cosine_schedule(args.warmup, args.steps))
    step_fn, _ = steps.make_train_step(cfg, plan, mesh, opt_cfg=opt,
                                       shape=shape)
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                          global_batch=args.batch, seed=args.seed)
    return cfg, plan, mesh, step_fn, data_cfg


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--tp", type=int, default=0)
    ap.add_argument("--dp", type=int, default=0)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--auto-resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--crash-at-step", type=int, default=0,
                    help="fault-injection: hard-exit at this step (tests)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from repro.checkpoint.manager import AsyncCheckpointer, CheckpointManager
    from repro.core import steps as _steps
    from repro.data import PackedBatches
    from repro.launch.compile_cache import enable_compile_cache
    from repro.runtime.ft import Heartbeat

    enable_compile_cache()
    cfg, plan, mesh, step_fn, data_cfg = build(args)
    state = _steps.init_train_state(cfg, plan, seed=args.seed)
    start_step = 0
    data_start_doc = 0
    data_buf = []

    ckpt = None
    saver = None
    if args.ckpt_dir:
        ckpt = CheckpointManager(args.ckpt_dir, keep=3)
        saver = AsyncCheckpointer(ckpt)
        if args.auto_resume and ckpt.latest_step() is not None:
            state, manifest = ckpt.restore(state)
            state = jax.tree_util.tree_map(jnp.asarray, state)
            start_step = manifest["step"]
            data_start_doc = manifest["extra"].get("doc_idx", 0)
            data_buf = manifest["extra"].get("buf", [])
            print(f"[resume] step {start_step} doc {data_start_doc}")

    pipe = PackedBatches(data_cfg, start_doc=data_start_doc, buf=data_buf)
    it = iter(pipe)
    jitted = jax.jit(step_fn, donate_argnums=(0,))
    hb = Heartbeat(timeout_s=600).start()

    t0 = time.time()
    for step in range(start_step, args.steps):
        batch = next(it)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        with mesh:
            state, stats = jitted(state, batch)
        hb.beat()
        if args.crash_at_step and step + 1 == args.crash_at_step:
            print(f"[fault-injection] hard exit at step {step + 1}",
                  flush=True)
            os._exit(17)
        if (step + 1) % args.log_every == 0 or step + 1 == args.steps:
            loss = float(stats["loss"])
            print(f"step {step + 1:5d} loss {loss:.4f} "
                  f"gnorm {float(stats['grad_norm']):.3f} "
                  f"({(time.time() - t0) / max(step + 1 - start_step, 1):.2f}"
                  f" s/step)", flush=True)
        if saver and ((step + 1) % args.ckpt_every == 0
                      or step + 1 == args.steps):
            saver.save(step + 1, state, extra=pipe.state())
    if saver:
        saver.wait()
    hb.stop()
    print("done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
