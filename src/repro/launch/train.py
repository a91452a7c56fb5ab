"""End-to-end training driver.

Runs any zoo arch (reduced/smoke configs on CPU; full configs on a real
cluster) with the whole substrate engaged: sharded train state, synthetic
data pipeline, LSE loss-curve monitor (divergence detection + ETA), periodic
checkpointing with atomic commit + GC, and crash-resume.

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --smoke --steps 200 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import checkpoint, configs
from repro.data import DataConfig, TokenPipeline
from repro.launch import mesh as mesh_lib
from repro.launch.compile_cache import use_compile_cache
from repro.models import get_model
from repro.sharding import rules
from repro.train import (AdamWConfig, LossCurveMonitor, TrainConfig,
                         init_train_state, make_train_step,
                         train_state_specs)


def build(args):
    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    model = get_model(cfg)
    tc = TrainConfig(
        optimizer=AdamWConfig(peak_lr=args.lr, warmup_steps=args.warmup,
                              total_steps=args.steps),
        microbatches=args.microbatches)
    return cfg, model, tc


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--target-loss", type=float, default=None)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg, model, tc = build(args)
    mesh = mesh_lib.make_host_mesh(model=args.model_parallel)
    print(f"[train] arch={cfg.arch} mesh={dict(mesh.shape)} "
          f"params≈{cfg.param_count()/1e6:.1f}M")

    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                      global_batch=args.global_batch)
    pipe = TokenPipeline(dcfg)

    state = init_train_state(model, jax.random.PRNGKey(args.steps))
    start_step = 0
    if args.ckpt_dir:
        last = checkpoint.latest_step(args.ckpt_dir)
        if last is not None:
            print(f"[train] resuming from step {last}")
            specs = train_state_specs(model)
            sh = rules.tree_shardings(
                mesh, specs, jax.eval_shape(lambda: state))
            state = checkpoint.restore(args.ckpt_dir, last, state,
                                       shardings=sh)
            start_step = last
            pipe.restore({"batch_idx": last * tc.microbatches or last})

    step_fn = jax.jit(make_train_step(model, tc), donate_argnums=(0,))
    monitor = LossCurveMonitor()

    t_last = time.time()
    for step in range(start_step, args.steps):
        batch = pipe.next()
        if cfg.family == "vlm":
            batch["extra_embeds"] = jnp.zeros(
                (args.global_batch // 1, cfg.n_image_tokens, cfg.d_model),
                jnp.bfloat16)
            batch["labels"] = jnp.concatenate(
                [jnp.zeros((batch["labels"].shape[0], cfg.n_image_tokens),
                           jnp.int32), batch["labels"]], axis=1)
            batch["loss_mask"] = jnp.concatenate(
                [jnp.zeros((batch["loss_mask"].shape[0], cfg.n_image_tokens),
                           jnp.float32), batch["loss_mask"]], axis=1)
        elif cfg.family == "audio":
            b = batch["tokens"].shape[0]
            batch = {"frames": jnp.zeros((b, args.seq_len, cfg.d_model),
                                         jnp.bfloat16),
                     "dec_tokens": batch["tokens"],
                     "labels": batch["labels"],
                     "loss_mask": batch["loss_mask"]}
        state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])
        monitor.observe(step, loss)

        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.time() - t_last
            t_last = time.time()
            extras = ""
            if monitor.ready:
                extras = f" fit_slope={monitor.slope_at(step):+.2e}"
                if monitor.diverging(step):
                    extras += " DIVERGING"
                if args.target_loss:
                    eta = monitor.eta_to(args.target_loss, step)
                    extras += f" eta_steps={eta}"
            print(f"[train] step {step} loss={loss:.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"({dt:.1f}s){extras}", flush=True)

        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            checkpoint.save(args.ckpt_dir, step + 1, state)
            checkpoint.gc_old(args.ckpt_dir, keep=3)
            print(f"[train] checkpointed step {step + 1}", flush=True)

    print(f"[train] done. final loss {loss:.4f}")
    return loss


if __name__ == "__main__":
    main()
