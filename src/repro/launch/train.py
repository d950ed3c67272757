"""End-to-end training driver.

  PYTHONPATH=src python -m repro.launch.train --arch deepseek_7b --smoke \
      --steps 50 --batch 8 --seq 128

``--smoke`` uses the reduced config on the host device count; the full
configs are exercised via the dry-run only (this container has 1 CPU
device).  The loop runs through `runtime/train_loop.py` — checkpointing,
straggler watchdog, resume — so the fault-tolerance path is the same one
a real cluster job uses.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.data import pipeline as datapipe
from repro.optim import adamw
from repro.runtime import train_loop


def make_lm_step(cfg, opt_cfg):
    from repro.models import transformer as tfm

    @jax.jit
    def step(state, batch):
        params, opt_state = state
        tokens, labels = batch
        loss, grads = jax.value_and_grad(tfm.train_loss)(
            params, tokens, labels, cfg)
        params, opt_state, stats = adamw.apply_updates(
            params, grads, opt_state, opt_cfg)
        return (params, opt_state), {"loss": loss, **stats}

    return step


def make_gnn_step(arch, cfg, opt_cfg):
    import repro.models.gnn as gnnmod
    m = getattr(gnnmod, arch)

    @jax.jit
    def step(state, batch):
        params, opt_state = state
        loss, grads = jax.value_and_grad(m.train_loss)(params, batch, cfg)
        params, opt_state, stats = adamw.apply_updates(
            params, grads, opt_state, opt_cfg)
        return (params, opt_state), {"loss": loss, **stats}

    return step


def make_recsys_step(cfg, opt_cfg):
    from repro.models.recsys import dcn

    @jax.jit
    def step(state, batch):
        params, opt_state = state
        loss, grads = jax.value_and_grad(dcn.train_loss)(params, batch, cfg)
        params, opt_state, stats = adamw.apply_updates(
            params, grads, opt_state, opt_cfg)
        return (params, opt_state), {"loss": loss, **stats}

    return step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()
    from repro.compile_cache import use_compile_cache
    use_compile_cache()

    mod = get_arch(args.arch)
    cfg = mod.SMOKE if args.smoke else mod.FULL
    opt_cfg = adamw.AdamWConfig(lr=args.lr, total_steps=args.steps,
                                warmup_steps=max(1, args.steps // 10))
    key = jax.random.PRNGKey(0)

    if mod.FAMILY == "lm":
        from repro.models import transformer as tfm
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
        params = tfm.init_params(key, cfg)
        dcfg = datapipe.TokenPipelineConfig(cfg.vocab, args.seq, args.batch)
        batch_fn = lambda step: jax.tree.map(
            jnp.asarray, datapipe.lm_batch(dcfg, step))
        step_fn = make_lm_step(cfg, opt_cfg)
    elif mod.FAMILY == "gnn":
        arch = args.arch.replace("-", "_")
        if arch in ("schnet", "mace"):
            b = datapipe.molecule_batch(16, 48, args.batch)
        else:
            b = datapipe.gnn_batch(256, 1024, getattr(cfg, "node_in", 8),
                                   d_edge=4 if arch == "meshgraphnet" else 0,
                                   n_classes=getattr(cfg, "out_dim", 5))
        b = jax.tree.map(jnp.asarray, b)
        batch_fn = lambda step: b
        m = getattr(__import__("repro.models.gnn", fromlist=[arch]), arch)
        params = m.init_params(key, cfg)
        step_fn = make_gnn_step(arch, cfg, opt_cfg)
    else:
        from repro.models.recsys import dcn
        params = dcn.init_params(key, cfg)
        batch_fn = lambda step: jax.tree.map(jnp.asarray, datapipe.recsys_batch(
            args.batch, cfg.n_dense, cfg.n_sparse, cfg.vocabs(), seed=step))
        step_fn = make_recsys_step(cfg, opt_cfg)

    opt_state = adamw.init_state(params)
    state = (params, opt_state)
    loop_cfg = train_loop.TrainLoopConfig(
        total_steps=args.steps, ckpt_dir=args.ckpt_dir,
        ckpt_every=max(10, args.steps // 3), log_every=5)
    start = 0
    if args.resume:
        state, start = train_loop.resume_or_init(args.ckpt_dir, state)
        print(f"resumed at step {start}")
    state, step, history, watchdog = train_loop.run(
        step_fn, state, batch_fn, loop_cfg, start_step=start)
    if history:
        print("first:", history[0])
        print("last: ", history[-1])
    print(f"done at step {step}; stragglers={watchdog.straggler_steps}")


if __name__ == "__main__":
    main()
