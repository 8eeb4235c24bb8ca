"""Elastic re-planned training: the glue between fault tolerance and the
real execution stack.

``fault_tolerance.TrainSupervisor`` is the jax-free retry-with-shrink state
machine; this module wires the same loop to the production pieces so a
``HostFailure`` (real, or injected via ``runtime.chaos``'s ``shard_loss``
site in the trainer's step loop) actually recovers:

  1. **re-mesh** — ``plan_elastic_mesh`` keeps the TP degree and shrinks
     data-parallel to the survivors; ``launch.mesh.mesh_from_plan`` builds
     the smaller (data, model) mesh on the surviving devices.
  2. **invalidate** — every plan-serving cache that closed over the old
     mesh is dropped (``invalidate_plans``): the five planner LRUs, the
     dispatch-level custom-VJP closures, and the bounded mesh-keyed EP
     executor caches.  The persistent plan store is NOT reset — its keys
     carry the ``|shards{n}`` suffix, so plans measured at the old shard
     count are unreachable at the new one by construction, and plans for
     the new count stay warm.  Telemetry counters survive so
     ``plan_mode_stats()`` shows the re-plan happening.
  3. **restore** — the next ``Trainer`` restores the latest checkpoint
     onto the new mesh (``Checkpointer.restore`` re-shards to the new
     shardings) and replays the deterministic data stream from the
     checkpointed step — recovery is exactly-once w.r.t. optimizer steps.

Import note: ``runtime.fault_tolerance``/``runtime.chaos`` stay jax-free;
this module imports the jax-side stack and is therefore NOT re-exported
from ``repro.runtime`` — import it as ``repro.runtime.elastic``.
"""
from __future__ import annotations

from .fault_tolerance import HostFailure, plan_elastic_mesh


def invalidate_plans() -> None:
    """Drop every cache that may have closed over the old mesh/shard count:
    planner LRUs, dispatch custom-VJP closures, EP executor closures.
    Keeps the persistent plan store (shard-count-suffixed keys) and the
    telemetry counters (the re-plan should be observable)."""
    from ..core.gemm.dispatch import clear_dispatch_caches
    from ..core.gemm.distributed import clear_executor_caches
    from ..core.gemm.tuner import clear_planner_caches
    clear_planner_caches()
    clear_dispatch_caches()
    clear_executor_caches()


class ElasticRunner:
    """Checkpoint-restart training on a shrinking mesh.

    Runs ``Trainer`` attempts until ``num_steps`` completes: each attempt
    plans the largest TP-preserving mesh for the surviving chips, rebuilds
    shardings for it, invalidates the stale executor caches, and resumes
    from the latest checkpoint with deterministic data replay.  A
    ``HostFailure`` out of the step loop (e.g. the ``shard_loss`` chaos
    site) shrinks the survivor count and retries; anything else
    propagates.  ``history`` records every attempt and failure;
    ``metrics_log`` accumulates the per-attempt step metrics in order."""

    def __init__(self, cfg, shape, opt_cfg=None, *, ckpt_dir,
                 model_parallel: int = 1, total_chips: int | None = None,
                 max_retries: int = 3, seed: int = 0, ckpt_every: int = 50,
                 log_every: int = 10, monitor=None):
        if not ckpt_dir:
            raise ValueError("elastic training requires a checkpoint dir "
                             "(recovery restores from it)")
        self.cfg = cfg
        self.shape = shape
        self.opt_cfg = opt_cfg
        self.ckpt_dir = ckpt_dir
        self.tp = model_parallel
        self.total_chips = total_chips
        self.max_retries = max_retries
        self.seed = seed
        self.ckpt_every = ckpt_every
        self.log_every = log_every
        self.monitor = monitor
        self.history: list[dict] = []
        self.metrics_log: list[dict] = []

    def _shardings(self, mesh) -> dict:
        from ..launch.specs import abstract_state, input_specs
        from ..launch.sharding import batch_specs, param_specs, to_shardings
        params_s, opt_s = abstract_state(self.cfg, self.shape, with_opt=True)
        batch_s = input_specs(self.cfg, self.shape)
        sh = {
            "params": to_shardings(param_specs(params_s, mesh), mesh),
            "opt": to_shardings(param_specs(opt_s, mesh), mesh),
            "batch": to_shardings(batch_specs(self.cfg, batch_s, mesh),
                                  mesh),
        }
        sh["batch_leaves"] = sh["batch"]
        return sh

    def run(self, num_steps: int):
        import jax

        from ..launch.mesh import mesh_from_plan
        from ..train.trainer import Trainer

        chips = self.total_chips or len(jax.devices())
        for attempt in range(self.max_retries + 1):
            plan = plan_elastic_mesh(chips, model_parallel=self.tp,
                                     global_batch=self.shape.global_batch)
            mesh = mesh_from_plan(plan)
            invalidate_plans()
            trainer = Trainer(self.cfg, self.shape, self.opt_cfg,
                              mesh=mesh, shardings=self._shardings(mesh),
                              seed=self.seed, ckpt_dir=self.ckpt_dir,
                              ckpt_every=self.ckpt_every,
                              monitor=self.monitor,
                              log_every=self.log_every)
            start = (trainer.ckpt.latest_step() or -1) + 1
            self.history.append({"attempt": attempt, "chips": plan.chips,
                                 "mesh": plan.mesh_shape, "start": start})
            try:
                result = trainer.run(num_steps)
                self.metrics_log.extend(trainer.metrics_log)
                return result
            except HostFailure as e:
                self.metrics_log.extend(trainer.metrics_log)
                self.history.append({"attempt": attempt,
                                     "failure": type(e).__name__,
                                     "lost_chips": e.lost_chips})
                chips = plan.chips - e.lost_chips
        raise RuntimeError("exhausted elastic retries")
