"""End-to-end training driver: data -> step -> checkpoint -> fault
tolerance, on any mesh.

Composes every substrate in the framework:
  * synthetic token pipeline (deterministic, resumable by step index);
  * jit'd train step with FSDP/TP shardings + running-sum microbatching;
  * async atomic checkpoints (CheckpointManager) + Supervisor restarts;
  * straggler detection hooks (per-step wall times);
  * optional error-feedback gradient compression for the cross-pod
    all-reduce (--compress int8|topk) — applied host-side here since this
    container has one physical device; on a real multi-pod deployment the
    compressor wraps the pod-axis psum.

Example (CPU, reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch gemma3-1b --smoke \
      --steps 20 --batch 8 --seq 128 --ckpt-dir /tmp/ck

XLA compute/comm overlap flags for real TPU runs (documented here, not
set on CPU): --xla_tpu_enable_async_collective_fusion=true
             --xla_tpu_overlap_compute_collective_tc=true
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.checkpoint import CheckpointManager
from repro.configs import ARCH_IDS, get_config
from repro.distributed import sharding as sh
from repro.launch import steps
from repro.mesh import make_mesh
from repro.models import build_model
from repro.optim import AdamW, cosine_schedule
from repro.optim import compress as C
from repro.runtime import StragglerDetector


def make_data_stream(cfg, batch, seq, microbatches, *, cycle: int = 4):
    """Deterministic resumable stream (repro.data.pipeline.DataPipeline)."""
    from repro.data.pipeline import DataPipeline

    return DataPipeline(
        cfg, batch=batch, seq=seq, microbatches=microbatches, cycle=cycle
    ).batch_at


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b",
                    help=f"one of {ARCH_IDS} or an ad-hoc registered config")
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--compress", default=None, choices=(None, "int8", "topk"))
    ap.add_argument("--mesh", default=None, help="e.g. 2x2 => (data,model)")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split("x"))
    else:
        shape = (len(jax.devices()), 1)
    mesh = make_mesh(shape, ("data", "model"))
    rules = steps.resolve_rules(cfg, mesh)
    opt = AdamW(learning_rate=cosine_schedule(args.lr, 5, args.steps))

    jitted, _ = steps.jit_train_step(
        model, opt, mesh, rules,
        microbatches=args.microbatches, batch=args.batch, seq=args.seq,
    )
    params = model.init(jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    residual = C.ef_init(params) if args.compress else None

    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start = 0
    if mgr is not None and mgr.latest_step() is not None:
        state, start = mgr.restore()
        params, opt_state = state["params"], state["opt"]
        start += 1
        print(f"[train] resumed from step {start}")

    data = make_data_stream(cfg, args.batch, args.seq, args.microbatches)
    straggler = StragglerDetector()
    losses = []
    for step in range(start, args.steps):
        t0 = time.perf_counter()
        batch = data(step)
        params, opt_state, metrics = jitted(params, opt_state, batch)
        if args.compress:
            # demonstrate the cross-pod path: compress what WOULD cross DCN
            grads_proxy = jax.tree_util.tree_map(
                lambda m: m, opt_state["mu"]
            )
            _, residual = C.ef_step(grads_proxy, residual, kind=args.compress)
        loss = float(metrics["loss"])
        losses.append(loss)
        dt = time.perf_counter() - t0
        straggler.record("worker0", dt)
        print(f"[train] step {step} loss {loss:.4f} ({dt * 1e3:.0f} ms)")
        if mgr is not None and step % args.ckpt_every == 0:
            mgr.save(step, {"params": params, "opt": opt_state})
    if mgr is not None:
        mgr.save(args.steps - 1, {"params": params, "opt": opt_state},
                 blocking=True)
    print(
        f"[train] done: first loss {losses[0]:.4f} -> last {losses[-1]:.4f}; "
        f"stragglers={straggler.stragglers()}"
    )
    return losses


if __name__ == "__main__":
    main()
