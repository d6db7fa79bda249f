"""The in-graph job: the gradients are exchanged by collectives inside
one jitted step.

    bps.init -> bps.make_mesh -> bps.DistributedOptimizer(optimizer)
             -> bps.build_train_step(loss, opt, mesh, donate=True)

over the chips the cell has, the configuration's per-chip batch on each.
On one chip `build_train_step` takes its plain-jit path and nothing is
exchanged.  The parameters are handed to the first step as a user's
script has them, fresh from the init and not yet on the mesh, so that what
the program does about it (today: a second compile, PERF.md) stays in
the set-up time.  The batch is put on the mesh once, sharded over `dp`,
and used for every step, as upstream's benchmark scripts do.
"""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec

import byteps_tpu as bps
from benchmark.harness import seeded


class Job:
    metric_prefix = ""      # <unit>_per_s

    def __init__(self, family, job: dict, traffic: dict, devices, seed: int,
                 trace=None):
        del traffic, trace      # no option of the mix; no spans of its own
        self.family, self.devices, self.seed = family, list(devices), seed
        self.n_shards = len(self.devices)
        self.samples_per_step = int(job["per_chip_batch"]) * self.n_shards
        # What the first losses are compared with: across chips the sum
        # runs in another order than on one; on one chip nothing is
        # exchanged and the reference check covers the step.
        self.plain_kind = "reduction_order" if self.n_shards > 1 else None

    def __enter__(self):
        bps.init()
        mesh = bps.make_mesh(devices=self.devices)
        opt = bps.DistributedOptimizer(self.family.optimizer())
        self._step = bps.build_train_step(self.family.loss, opt, mesh,
                                          donate=True)
        self.params = seeded.params(self.family, self.seed)
        self.opt_state = jax.jit(opt.init)(self.params)
        self.batch = jax.device_put(
            seeded.batch(self.family, self.seed, self.samples_per_step),
            NamedSharding(mesh, PartitionSpec("dp")))
        return self

    def __exit__(self, *exc):
        bps.shutdown()

    def step(self):
        """Dispatches one training step; returns its loss, not waited
        for."""
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            self.params, self.opt_state, loss = self._step(
                self.params, self.opt_state, self.batch)
        return loss

    def checked_step(self):
        return self.step(), {}

    def extras(self) -> dict:
        return {}
