"""Training launcher: end-to-end fault-tolerant loop.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b \
        --steps 50 --reduced --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

--reduced runs the smoke-scale config on CPU; on a real cluster the same
loop runs the full config under the production mesh (launch/mesh.py).
The loop wires together: deterministic step-indexed data (exact resume),
async atomic checkpoints, NaN rollback, straggler detection, preemption
checkpointing.
"""

from __future__ import annotations

import argparse
import time

import jax

from ..configs import get_config, reduced_config
from ..data.pipeline import DataConfig, Prefetcher
from ..distributed.sharding import default_rules, use_rules
from .mesh import make_mesh
from ..models import ModelConfig
from ..train import checkpoint as ckpt
from ..train.fault import FaultConfig, Preemption, RunReport, StepTimer, is_bad
from ..train.optimizer import OptConfig
from ..train.train_step import TrainConfig, make_train_state, train_step


def train_loop(cfg: ModelConfig, tcfg: TrainConfig, dcfg: DataConfig,
               fcfg: FaultConfig, steps: int, ckpt_dir: str | None = None,
               preemption: Preemption | None = None,
               inject_nan_at: int | None = None,
               log_every: int = 10) -> RunReport:
    report = RunReport()
    preemption = preemption or Preemption()

    rng = jax.random.PRNGKey(0)
    params, opt_state = make_train_state(rng, cfg)
    start = 0
    if ckpt_dir:
        last = ckpt.latest_step(ckpt_dir)
        if last is not None:
            params, opt_state, _ = ckpt.restore(ckpt_dir, last, params,
                                                opt_state)
            start = last
            print(f"resumed from step {last}")

    step_fn = jax.jit(lambda p, o, b: train_step(p, o, b, cfg, tcfg),
                      donate_argnums=(0, 1))
    timer = StepTimer(fcfg)
    pf = Prefetcher(dcfg, start)
    rollbacks = 0
    step = start
    pending_save = None
    try:
        while step < steps:
            s, host_batch = pf.next()
            if s != step:
                continue  # skip stale prefetches after rollback
            t0 = time.time()
            batch = {k: jax.numpy.asarray(v) for k, v in host_batch.items()}
            new_params, new_opt, metrics = step_fn(params, opt_state, batch)
            metrics = {k: float(v) for k, v in metrics.items()}
            if inject_nan_at is not None and step == inject_nan_at:
                metrics["loss"] = float("nan")
                inject_nan_at = None
            if is_bad(metrics):
                # rollback: reload last checkpoint, skip the bad batch
                rollbacks += 1
                report.rollbacks += 1
                if ckpt_dir is None or rollbacks > fcfg.max_rollbacks:
                    raise RuntimeError("unrecoverable divergence")
                if pending_save is not None:
                    pending_save.join()  # roll back to the newest checkpoint
                    pending_save = None
                last = ckpt.latest_step(ckpt_dir)
                params, opt_state = make_train_state(rng, cfg)
                if last is not None:
                    params, opt_state, _ = ckpt.restore(ckpt_dir, last,
                                                        params, opt_state)
                pf.close()
                step = (last or 0) + 1  # deterministic skip past the bad batch
                pf = Prefetcher(dcfg, step)
                print(f"rollback -> step {step}")
                continue
            params, opt_state = new_params, new_opt
            dt = time.time() - t0
            if timer.record(step, dt):
                report.stragglers += 1
            step += 1
            report.steps_run += 1
            if step % log_every == 0 or step == steps:
                print(f"step {step:5d} loss={metrics['loss']:.4f} "
                      f"lr={metrics.get('lr', 0):.2e} {dt*1e3:.0f}ms",
                      flush=True)
            want_ckpt = ckpt_dir and (step % fcfg.checkpoint_every == 0
                                      or preemption.requested
                                      or step == steps)
            if want_ckpt:
                if pending_save is not None:
                    pending_save.join()
                pending_save = ckpt.save_async(ckpt_dir, step, params,
                                               opt_state)
                report.checkpoints += 1
                if preemption.requested:
                    break
    finally:
        if pending_save is not None:
            pending_save.join()
        pf.close()
    report.final_step = step
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args()

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    tcfg = TrainConfig(microbatches=args.microbatches,
                       opt=OptConfig(peak_lr=args.lr, warmup_steps=5,
                                     stable_steps=max(args.steps - 10, 5),
                                     decay_steps=5))
    dcfg = DataConfig(seq_len=args.seq, global_batch=args.batch,
                      vocab_size=cfg.vocab_size,
                      frames_dim=cfg.d_model if cfg.is_encdec else 0)
    fcfg = FaultConfig(checkpoint_every=max(args.steps // 4, 5))
    mesh = make_mesh((1, 1), ("data", "model"))
    with mesh, use_rules(default_rules(mesh)):
        report = train_loop(cfg, tcfg, dcfg, fcfg, args.steps,
                            ckpt_dir=args.ckpt_dir)
    print(f"done: {report}")


if __name__ == "__main__":
    main()
