"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs a real (small or full) training job on whatever devices exist:
mesh from the live device set (elastic), sort-bucketed data pipeline,
checkpoint/restart via the fault-tolerance manager. On this CPU container
it trains reduced configs end-to-end (examples/train_moe.py drives a
~100M-class run); on a TPU pod the same entry point runs the full config.
"""
import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.ckpt import CheckpointManager
from repro.configs.registry import get_config, smoke_config
from repro.data.pipeline import DataConfig, PackedLoader
from repro.ft.manager import RestartManager
from repro.launch.mesh import make_mesh_for
from repro.models.model import Model
from repro.optim.adamw import OptConfig
from repro.sharding import rules
from repro.sharding.spec import from_mesh
from repro.train.step import TrainConfig, init_train_state, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--full-config", action="store_true",
                    help="use the full architecture (TPU pods)")
    ap.add_argument("--width", type=int, default=0,
                    help="override d_model of the smoke config (scale up)")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch) if args.full_config else smoke_config(args.arch)
    if args.width or args.layers:
        import dataclasses

        kw = {}
        if args.width:
            d = args.width
            kw.update(d_model=d, d_ff=4 * d,
                      d_head=max(16, d // max(cfg.n_heads, 1)))
            if cfg.lru_width:
                kw["lru_width"] = d
        if args.layers:
            period = cfg.segments[0][0]
            kw["segments"] = ((period, args.layers),)
            kw["n_layers"] = args.layers * len(period)
        cfg = dataclasses.replace(cfg, **kw)

    n_dev = len(jax.devices())
    mesh = make_mesh_for(n_dev) if n_dev > 1 else None
    axes = from_mesh(mesh) if mesh is not None else None
    model = Model(cfg, axes)
    tcfg = TrainConfig(opt=OptConfig(
        name=cfg.optimizer, peak_lr=args.lr, warmup_steps=max(args.steps // 20, 5),
        total_steps=args.steps, state_dtype=cfg.opt_state_dtype,
    ))

    params, opt_state = init_train_state(model, tcfg, jax.random.key(0))
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    print(f"[train] {cfg.name}: {n_params:,} params on {n_dev} device(s)")

    step_fn = make_train_step(model, tcfg)
    if mesh is not None:
        pspecs = rules.param_specs(jax.eval_shape(lambda: params), cfg, axes)
        with jax.set_mesh(mesh):
            step_fn = jax.jit(step_fn, donate_argnums=(0, 1))
    else:
        step_fn = jax.jit(step_fn, donate_argnums=(0, 1))

    dcfg = DataConfig(seq_len=args.seq_len, global_batch=args.global_batch,
                      grad_accum=args.grad_accum, vocab=cfg.vocab,
                      bucket_docs=max(512, args.global_batch * 16))
    loader = PackedLoader(dcfg, cfg)
    it = iter(loader)

    ckpt = CheckpointManager(args.ckpt_dir, keep=3)
    start_step = 0
    if args.resume:
        restored, ck_step = ckpt.restore_latest((params, opt_state))
        if restored is not None:
            (params, opt_state), start_step = restored, ck_step
            print(f"[train] resumed from step {start_step}")

    mgr = RestartManager(ckpt, save_every=args.save_every)

    def wrapped_step(state, step, batch):
        p, o = state
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        p, o, metrics = step_fn(p, o, jnp.int32(step), batch)
        return (p, o), metrics

    t_start = time.time()

    def on_metrics(step, metrics):
        if "loss" in metrics and step % args.log_every == 0:
            toks = args.global_batch * args.seq_len * args.grad_accum
            dt = time.time() - t_start
            print(f"[train] step {step}: loss={float(metrics['loss']):.4f} "
                  f"acc={float(metrics['accuracy']):.3f} "
                  f"gnorm={float(metrics['grad_norm']):.2f} "
                  f"({toks * (step - start_step + 1) / max(dt, 1e-9):.0f} tok/s)")

    (params, opt_state), final = mgr.run(
        (params, opt_state), start_step, args.steps,
        wrapped_step, lambda s: next(it), on_metrics,
    )
    ckpt.save_async(final, (params, opt_state))
    ckpt.wait()
    print(f"[train] done at step {final}; recoveries={mgr.recoveries} "
          f"stragglers={mgr.watchdog.stragglers}")


if __name__ == "__main__":
    main()
