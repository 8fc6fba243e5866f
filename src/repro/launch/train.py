"""Training driver: data pipeline + jitted step + checkpoint/restart +
failure handling + straggler monitoring.

Local runs use whatever devices exist (``make_host_mesh``); on a pod the
same driver runs under the production mesh.  The loop survives injected
failures by restoring the latest checkpoint — onto a *smaller* elastic
mesh if devices were lost — and continues the exact data stream.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
      --steps 50 --batch 8 --seq 128 --reduced --ckpt /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import Checkpointer
from ..configs import get_config
from ..data import DataConfig, DataPipeline
from ..ft import FailureInjector, StragglerMonitor
from ..ft.elastic import SimulatedFailure
from ..models.model_zoo import Model
from ..train import optimizer as opt
from ..train.train_loop import (TrainConfig, make_train_state,
                                make_train_step, split_microbatches)
from .compile_cache import enable_compile_cache


def run(arch: str, *, steps: int = 20, batch: int = 8, seq: int = 128,
        reduced: bool = True, ckpt_dir: str | None = None,
        ckpt_every: int = 10, accum: int = 1, lr: float = 3e-4,
        fail_at: tuple[int, ...] = (), seed: int = 0,
        log_every: int = 5, compress_grads: bool = False) -> dict:
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    model = Model(cfg)
    tcfg = TrainConfig(opt=opt.OptConfig(lr=lr, warmup_steps=max(2, steps // 10),
                                         total_steps=steps),
                       accum=accum, remat=not reduced,
                       compress_grads=compress_grads)
    data = DataPipeline(DataConfig(vocab=cfg.vocab, seq_len=seq,
                                   global_batch=batch, seed=seed))
    ckpt = Checkpointer(ckpt_dir) if ckpt_dir else None
    injector = FailureInjector(fail_at)
    monitor = StragglerMonitor()

    state = make_train_state(model, jax.random.key(seed), tcfg)
    step_fn = jax.jit(make_train_step(model, tcfg), donate_argnums=(0,))
    start = 0
    if ckpt and ckpt.latest_step() is not None:
        state, manifest = ckpt.restore(state)
        start = manifest["step"]
        data.load_state_dict(manifest["extra"].get("data", {"step": start}))
        print(f"[train] restored step {start}", flush=True)

    losses = []
    step = start
    while step < steps:
        try:
            injector.check(step)
            monitor.step_start()
            raw = data.batch_at(step)
            batch_dev = {k: jnp.asarray(v) for k, v in raw.items()}
            batch_dev = split_microbatches(batch_dev, tcfg.accum)
            state, metrics = step_fn(state, batch_dev)
            if monitor.step_end(step):
                print(f"[train] step {step}: straggler flagged "
                      f"(rate {monitor.straggle_rate:.0%})", flush=True)
            loss = float(metrics["loss"])
            losses.append(loss)
            if step % log_every == 0:
                print(f"[train] step {step}: loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f}",
                      flush=True)
            step += 1
            data.step = step
            if ckpt and step % ckpt_every == 0:
                ckpt.save_async(step, state,
                                extra={"data": data.state_dict(),
                                       "arch": arch})
        except SimulatedFailure as exc:
            print(f"[train] {exc}; restoring from checkpoint", flush=True)
            if ckpt is None or ckpt.latest_step() is None:
                print("[train] no checkpoint; restarting from scratch",
                      flush=True)
                state = make_train_state(model, jax.random.key(seed), tcfg)
                step = 0
            else:
                ckpt.wait()
                state, manifest = ckpt.restore(state)
                step = manifest["step"]
                data.load_state_dict(
                    manifest["extra"].get("data", {"step": step}))
                print(f"[train] resumed at step {step}", flush=True)
    if ckpt:
        ckpt.wait()
    return {"final_loss": losses[-1] if losses else float("nan"),
            "losses": losses, "straggle_rate": monitor.straggle_rate}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt", dest="ckpt_dir")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[])
    ap.add_argument("--compress-grads", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()
    out = run(args.arch, steps=args.steps, batch=args.batch, seq=args.seq,
              reduced=args.reduced, ckpt_dir=args.ckpt_dir,
              ckpt_every=args.ckpt_every, accum=args.accum, lr=args.lr,
              fail_at=tuple(args.fail_at),
              compress_grads=args.compress_grads)
    print(f"[train] done: final loss {out['final_loss']:.4f}")


if __name__ == "__main__":
    main()
