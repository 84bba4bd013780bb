"""The system under test, assembled as `repro.launch.train.run` assembles it.

`train.run` only knows registered archs and runs a fixed number of steps, so
the benchmark builds the same objects itself: the model config the
configuration's family reads from its file, the mesh (none on one chip, a
pure ("data",) mesh over every chip otherwise), the strategy from the
launcher's own flags, `DQConfig.from_strategy` with OMD and the update
message, `DQGAN`, `trainer.init`, and the step jitted with the launcher's
static and donated arguments. `Program.step_once` is the body of the
launcher's loop without its logging: ask the schedule whether this step
exchanges, call the step, wait for its metrics.
"""
from __future__ import annotations

import argparse
import time
from contextlib import nullcontext
from dataclasses import dataclass
from typing import Any

import jax

from repro import strategy as strategy_api
from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN
from repro.models import build
from repro.parallel import sharding as shd

from pool import seed_key


def parse_strategy(flags, worker_axes):
    """The launcher's strategy flags, resolved as the launcher resolves
    them."""
    ap = argparse.ArgumentParser()
    strategy_api.add_strategy_args(ap)
    return strategy_api.strategy_from_args(ap.parse_args(list(flags)),
                                           worker_axes=worker_axes)


@dataclass
class Program:
    cfg: Any           # the model config `repro.models.build` takes
    trainer: DQGAN
    sched: Any
    step: Any          # jax.jit(trainer.step, static_argnums=(3,), donate=(0,))
    state: Any
    key: Any
    mesh: Any
    n_workers: int
    i: int = 0         # the launcher's step counter

    def context(self):
        return jax.set_mesh(self.mesh) if self.mesh is not None \
            else nullcontext()

    def step_once(self, batch):
        """One launcher step. Returns (metrics, seconds spent in the call
        that enqueues the step, synced seconds of the whole step)."""
        do_exchange = self.sched.is_exchange_step(self.i)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench/dispatch"):
            out = self.step(self.state, batch, self.key, do_exchange)
        t1 = time.perf_counter()
        self.state = out.state
        with jax.profiler.TraceAnnotation("bench/sync"):
            jax.block_until_ready(out.metrics)
        t2 = time.perf_counter()
        self.i += 1
        return out.metrics, t1 - t0, t2 - t0


def build_program(family, config: dict, traffic: dict, seed: int,
                  n_devices: int) -> Program:
    """The launcher's step for the model that `family` reads from the
    configuration file `config`, under the traffic mix `traffic`."""
    cfg = family.program_config(config)
    bundle = build(cfg)
    worker_axes = ("data",) if n_devices > 1 else ()
    strat = parse_strategy(traffic["flags"], worker_axes)
    key = seed_key(seed)
    params = jax.jit(bundle.init)(key)
    mesh = pspecs = bspec = None
    if n_devices > 1:
        from jax.sharding import AxisType, PartitionSpec as P

        if shd.uses_axis(shd.param_specs(params, cfg, "dp"), "model"):
            raise ValueError(f"{cfg.name}: a leaf shards over 'model'; the "
                             "benchmark drives the pure data mesh only")
        mesh = jax.make_mesh((n_devices,), ("data",),
                             axis_types=(AxisType.Auto,))
        bspec = P(("data",))
    dq = DQConfig.from_strategy(strat, optimizer="omd", lr=traffic["lr"],
                                message="update")
    if mesh is not None:
        pspecs = shd.param_specs(params, cfg, "dp", mesh)
        params = jax.tree.map(jax.device_put, params,
                              shd.shardings(pspecs, mesh))
    trainer = DQGAN(field_fn=bundle.field_fn, dq=dq, mesh=mesh,
                    param_specs=pspecs, batch_spec=bspec)
    state = trainer.init(params)
    step = jax.jit(trainer.step, static_argnums=(3,), donate_argnums=(0,))
    return Program(cfg=cfg, trainer=trainer, sched=strat.schedule.runtime(),
                   step=step, state=state, key=key, mesh=mesh,
                   n_workers=max(trainer.n_workers, 1))
