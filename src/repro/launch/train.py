"""Distributed training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --steps 50 \
        [--mesh 2x4] [--smoke] [--accum 2] [--ckpt-dir /tmp/ck]

On a real TPU fleet this process runs per-host under `jax.distributed`
initialization (one line, env-driven) and the same code shards over the full
mesh; in this container it runs on however many local (or
XLA_FLAGS-faked) devices are available. `--smoke` uses the reduced config.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--mesh", default="",
                    help="DxM data x model, e.g. 2x4; default all x 1")
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full-config", dest="smoke", action="store_false",
                    help="use the full assigned config (needs real HBM)")
    ap.add_argument("--remat", default="nothing")
    args = ap.parse_args(argv)
    from repro.launch import compile_cache
    compile_cache.enable()

    from jax.sharding import Mesh, NamedSharding
    from repro.configs import get_config, get_smoke_config
    from repro.models import model as MD
    from repro.models.config import ShapeConfig
    from repro.optim import AdamWConfig, adamw_init
    from repro.optim.schedule import cosine_schedule
    from repro.sharding.rules import make_rules
    from repro.train import TrainLoopConfig, train_loop
    from repro.train.step import make_train_step

    devs = jax.devices()
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
    else:
        d, m = len(devs), 1
    mesh = Mesh(np.asarray(devs[:d * m]).reshape(d, m), ("data", "model"))
    rules = make_rules(mesh) if d * m > 1 else None
    print(f"mesh: data={d} model={m}; arch={args.arch} "
          f"({'smoke' if args.smoke else 'full'} config)")

    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    import dataclasses
    cfg = dataclasses.replace(cfg, dtype="float32")
    shape = ShapeConfig("train", args.seq, args.batch, "train")
    params = MD.init_params(cfg, jax.random.PRNGKey(0))
    opt_state = adamw_init(params)
    if rules is not None:
        pshard = rules.param_shardings(params)
        params = jax.device_put(params, pshard)
        opt_state = jax.device_put(
            opt_state, {"m": pshard, "v": pshard,
                        "step": NamedSharding(mesh,
                                              jax.sharding.PartitionSpec())})

    opt_cfg = AdamWConfig(lr=cosine_schedule(args.lr, 10, args.steps))
    step = jax.jit(make_train_step(cfg, opt_cfg, rules, args.remat,
                                   accum_steps=args.accum),
                   donate_argnums=(0, 1))

    def put_batch(b):
        if rules is None:
            return {k: jax.numpy.asarray(v) for k, v in b.items()}
        return {k: jax.device_put(v, rules.input_sharding(v.shape, k))
                for k, v in b.items()}

    out = train_loop(step, params, opt_state, cfg, shape,
                     TrainLoopConfig(steps=args.steps,
                                     ckpt_dir=args.ckpt_dir,
                                     ckpt_every=25, log_every=10),
                     put_batch=put_batch)
    h = out["history"]
    print(f"final: loss {h[0]['loss']:.4f} -> {h[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
