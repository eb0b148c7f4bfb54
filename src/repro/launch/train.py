"""Training launcher: ``python -m repro.launch.train --arch smollm-360m``.

Single-process only in this container; at real scale this process runs per
host (jax.distributed.initialize) and everything below is unchanged — the
mesh axes span hosts, the data loader shards by host id, and the
checkpoint/restart loop in ``repro.training.loop`` handles preemptions.
"""
from __future__ import annotations

import argparse

import jax
from jax.sharding import PartitionSpec as P

import repro.configs as configs
from repro import cache_dirs, compat
from repro.configs.base import OptimizerConfig, ParallelConfig, RunConfig
from repro.data.synthetic import SyntheticConfig, SyntheticDataset
from repro.distributed import context, sharding
from repro.training import loop
from repro.training.train_step import init_state, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--checkpoint-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--mesh", default=None,
                    help="e.g. 2x4 → mesh (data=2, model=4); default: all "
                         "devices on the data axis")
    args = ap.parse_args(argv)
    cache_dirs.enable_compile_cache()

    cfg = (configs.get_smoke(args.arch) if args.smoke
           else configs.get(args.arch))
    run_cfg = RunConfig(
        model=cfg,
        optimizer=OptimizerConfig(lr=args.lr, warmup_steps=20,
                                  total_steps=args.steps),
        parallel=ParallelConfig(microbatches=args.microbatches),
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
    )

    devices = jax.devices()
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        mesh = compat.make_mesh(dims, ("data", "model")[:len(dims)])
    else:
        mesh = compat.make_mesh((len(devices), 1), ("data", "model"))

    params, opt_state, axes = init_state(run_cfg, jax.random.PRNGKey(run_cfg.seed))
    par = sharding.derive_parallel(cfg, mesh, run_cfg.parallel)
    p_sh = sharding.param_sharding(axes, cfg, par, mesh)
    params = jax.device_put(params, p_sh)
    opt_sh = compat.tree_map(lambda _: None, opt_state)  # follow params
    step_fn = jax.jit(make_train_step(run_cfg), donate_argnums=(0, 1))

    ds = SyntheticDataset(SyntheticConfig(
        vocab_size=cfg.real_vocab_size or cfg.vocab_size,
        seq_len=args.seq_len, global_batch=args.global_batch,
        seed=run_cfg.seed))

    ctx = context.ShardContext(mesh=mesh, par=par)
    with mesh, context.use(ctx):
        params, opt_state, history = loop.run(
            run_cfg, steps=args.steps, train_step=step_fn,
            params=params, opt_state=opt_state, dataset=ds,
            batch_sharding=compat.named_sharding(mesh, P(par.data_axes)))
    losses = [h["loss"] for h in history if "loss" in h]
    if losses:
        print(f"first loss {losses[0]:.4f} → last loss {losses[-1]:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
