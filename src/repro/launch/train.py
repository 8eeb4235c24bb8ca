"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b-smoke \
        --steps 20 [--seq 128 --batch 8] [--mesh 2x4] [--ckpt /tmp/ck] \
        [--elastic [--model-parallel 1]]

On real hardware the same entry runs under ``jax.distributed.initialize``
(multi-host); in this container a ``--mesh AxB`` spawns that many host
devices (set before jax import via XLA_FLAGS).

``--elastic`` runs under ``runtime.elastic.ElasticRunner`` instead of a
bare ``Trainer``: a ``HostFailure`` mid-run (real, or injected with
``REPRO_CHAOS="shard_loss@N:chips=K"``) shrinks the mesh to the
survivors, re-plans the placed GEMMs, restores the latest checkpoint and
resumes with deterministic data replay.  Requires ``--ckpt``."""
from __future__ import annotations

import argparse
import os
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--mesh", default=None, help="e.g. 2x4 = (data, model)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--elastic", action="store_true",
                    help="recover from HostFailure by re-meshing onto the "
                         "survivors (checkpoint-restart; needs --ckpt)")
    ap.add_argument("--model-parallel", type=int, default=None,
                    help="TP degree preserved across elastic re-meshes "
                         "(default: the model axis of --mesh, else 1)")
    args = ap.parse_args()

    if args.mesh:
        n = 1
        for part in args.mesh.split("x"):
            n *= int(part)
        os.environ.setdefault(
            "XLA_FLAGS", f"--xla_force_host_platform_device_count={n}")

    import jax

    from ..configs import get_config
    from ..configs.base import ShapeConfig
    from ..optim.adamw import OptConfig
    from ..runtime.compile_cache import enable_compile_cache
    from ..train.trainer import Trainer
    from .mesh import make_mesh
    from .sharding import (batch_specs, param_specs, to_shardings)
    from .specs import abstract_state, input_specs

    enable_compile_cache()
    cfg = get_config(args.arch)
    shape = ShapeConfig("cli", seq_len=args.seq, global_batch=args.batch,
                        kind="train")

    if args.elastic:
        from ..runtime.elastic import ElasticRunner
        dims = tuple(int(x) for x in args.mesh.split("x")) if args.mesh \
            else (len(jax.devices()),)
        tp = args.model_parallel or (dims[1] if len(dims) == 2 else 1)
        opt_cfg = OptConfig(lr=args.lr,
                            warmup_steps=min(100, args.steps // 10 + 1),
                            total_steps=args.steps)
        runner = ElasticRunner(cfg, shape, opt_cfg, ckpt_dir=args.ckpt,
                               model_parallel=tp, seed=args.seed)
        runner.run(args.steps)
        for h in runner.history:
            print("elastic:", h)
        print("training done")
        return

    mesh = None
    shardings = {}
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        axes = ("data", "model")[:len(dims)] if len(dims) == 2 else ("data",)
        mesh = make_mesh(dims, axes)
        params_s, opt_s = abstract_state(cfg, shape, with_opt=True)
        batch_s = input_specs(cfg, shape)
        with mesh:
            shardings = {
                "params": to_shardings(param_specs(params_s, mesh), mesh),
                "opt": to_shardings(param_specs(opt_s, mesh), mesh),
                "batch": to_shardings(batch_specs(cfg, batch_s, mesh), mesh),
            }
            shardings["batch_leaves"] = shardings["batch"]

    opt_cfg = OptConfig(lr=args.lr, warmup_steps=min(100, args.steps // 10 + 1),
                        total_steps=args.steps)
    trainer = Trainer(cfg, shape, opt_cfg, mesh=mesh, shardings=shardings,
                      seed=args.seed, ckpt_dir=args.ckpt)
    trainer.run(args.steps)
    print("training done")


if __name__ == "__main__":
    main()
