"""Distributed training launcher.

On a real TPU pod this runs under the production mesh; on CPU it runs the
same code path on a small test mesh (set
XLA_FLAGS=--xla_force_host_platform_device_count=8 to exercise SPMD).

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --reduced --steps 20 --mesh 2x4
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced_config
from repro.configs.base import ShapeConfig
from repro.distributed import axes as axlib
from repro.distributed.sharding import batch_pspecs, make_plan, param_pspecs
from repro.launch.mesh import auto_mesh
from repro.models import build_model
from repro.train.data import DataConfig, SyntheticLM
from repro.train.fault_tolerance import FaultToleranceConfig, FaultTolerantRunner
from repro.train.optimizer import AdamWConfig, adamw_init
from repro.train.trainer import make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true",
                    help="laptop-scale same-family config")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL, e.g. 2x4")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--ckpt-dir", default="ckpts")
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = auto_mesh((d, m), ("data", "model"))
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    plan = make_plan(cfg, mesh, "train", shape, variant=args.variant)
    c = plan.cfg

    model = build_model(c, remat=False)
    print(f"training {c.name} ({c.param_count()/1e6:.1f} M params) on "
          f"mesh {dict(mesh.shape)} variant={args.variant}")

    params = model.init(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    p_specs = plan.tree_shardings(param_pspecs(
        jax.eval_shape(lambda: params), plan.mapping))
    params = jax.tree.map(jax.device_put, params, p_specs)
    opt = {"mu": jax.tree.map(jax.device_put, opt["mu"], p_specs),
           "nu": jax.tree.map(jax.device_put, opt["nu"], p_specs),
           "step": opt["step"]}

    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=10,
                          total_steps=max(100, args.steps))
    raw_step = make_train_step(model, opt_cfg)

    def fn(p, o, b):
        with axlib.axis_env(mesh, plan.mapping):
            return raw_step(p, o, b)

    step = jax.jit(fn, donate_argnums=(0, 1))
    ds = SyntheticLM(DataConfig(vocab_size=c.vocab_size, seq_len=args.seq,
                                global_batch=args.batch, seed=0))
    runner = FaultTolerantRunner(step, FaultToleranceConfig(
        ckpt_dir=args.ckpt_dir, ckpt_every=max(10, args.steps // 2)))
    params, opt, start = runner.try_restore(params, opt)
    if start >= args.steps:
        print(f"done: checkpoint already at step {start} (>= --steps)")
        return
    with mesh:
        out = runner.run(params, opt, ds.batch, n_steps=args.steps,
                         start_step=start)
    if out["losses"]:
        print(f"done: step {out['final_step']}, loss "
              f"{out['losses'][0]:.3f} -> {out['losses'][-1]:.3f}")
    else:
        print(f"done: step {out['final_step']} (no new steps)")


if __name__ == "__main__":
    main()
