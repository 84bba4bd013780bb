"""Regenerate the committed mix-trainer HLO fixtures (test_obs_hlo.py).

Run after a deliberate change to the compiled step graph::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python tests/fixtures/regen_mix_8dev.py

Writes the gzipped optimized (post-SPMD, per-device) HLO of the mix
trainer's jitted step — exchange variants for every_step / local_k(4) /
delayed(τ=4) plus the local_k mid-round variant and the split-phase
delayed(τ=4) ``exchange.overlap=True`` lowering — and the
mix_8dev_expected.json expectations the tests pin (collective
summaries, scope-phase op counts, ring-parameter count, ledger bytes,
and the overlap variant's schedule-structure verdict).
"""
import gzip
import json
import os

import jax
from jax.sharding import AxisType, PartitionSpec as P

from repro.configs.base import DQConfig
from repro.core.dqgan import DQGAN
from repro.launch.hlo_analysis import scope_costs
from repro.models.gan import GANConfig, gan_field_fn, mlp_gan_init
from repro.obs import hlo as ohlo
from repro.strategy import (
    Compression,
    ExchangePlan,
    Observability,
    Schedule,
    Strategy,
)

FIX = os.path.dirname(os.path.abspath(__file__))


def build(schedule, mesh, cfg, overlap=False):
    strat = Strategy(
        compression=Compression(plan="uniform", bucket_mb=0.03),
        exchange=ExchangePlan(kind="two_phase", spmd="shard_map",
                              worker_axes=("data",), overlap=overlap),
        schedule=schedule,
        observability=Observability(spans=True))
    dq = DQConfig.from_strategy(strat, optimizer="omd", lr=1e-2)
    return DQGAN(field_fn=gan_field_fn(cfg), dq=dq, mesh=mesh,
                 batch_spec=P(("data",)))


def main():
    assert jax.device_count() >= 8, \
        "run with XLA_FLAGS=--xla_force_host_platform_device_count=8"
    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    cfg = GANConfig(name="mix", image_size=0, data_dim=2, latent_dim=16,
                    hidden=128)
    key = jax.random.key(0)
    params = mlp_gan_init(key, cfg)
    batch = {"real": jax.random.normal(key, (64, 2))}
    expected = {}

    def dump(fname, txt):
        txt = ohlo.strip_locations(txt)
        with gzip.open(os.path.join(FIX, fname), "wt",
                       compresslevel=9) as fh:
            fh.write(txt)
        expected[fname] = {
            "collectives": ohlo.collective_summary(txt),
            "scope_phases": {k: v["ops"]
                             for k, v in scope_costs(txt).items()},
        }

    for name, schedule, overlap in [
            ("every_step", Schedule(), False),
            ("local_k4", Schedule.local_k(4), False),
            ("delayed_tau4", Schedule.delayed(tau=4), False),
            ("delayed_tau4_overlap", Schedule.delayed(tau=4), True)]:
        tr = build(schedule, mesh, cfg, overlap=overlap)
        with jax.set_mesh(mesh):
            st = tr.init(params)
            step = jax.jit(tr.step, static_argnums=(3,))
            ex = ohlo.compiled_text(step, st, batch, jax.random.key(7),
                                    True)
            dump(f"mix_{name}_8dev.hlo.txt.gz", ex)
            if name == "local_k4":
                mid = ohlo.compiled_text(step, st, batch,
                                         jax.random.key(7), False)
                dump("mix_local_k4_mid_8dev.hlo.txt.gz", mid)
        if name.startswith("delayed_tau4"):
            expected[f"mix_{name}_8dev.hlo.txt.gz"]["ring_params"] = \
                len(ohlo.ring_parameters(ex, 4))
        if overlap:
            # the split-phase lowering's structural invariant, pinned:
            # every exchange-scoped collective is dataflow-independent
            # of the field phase (async -start/-done pairs only appear
            # on GPU/TPU backends, so they are reported, not required)
            indep = ohlo.exchange_field_independence(ex)
            expected[f"mix_{name}_8dev.hlo.txt.gz"]["independence"] = {
                "exchange_collectives": indep["exchange_collectives"],
                "tainted": indep["tainted"], "ok": indep["ok"],
            }

    expected["n_param_leaves"] = len(jax.tree.leaves(params))
    led = build(Schedule(), mesh, cfg).comm_ledger(params)
    expected["ledger"] = {
        "wire_bytes_per_step": led.wire_bytes_per_step,
        "carried_bytes_per_step": led.carried_bytes_per_step,
        "n_workers": led.n_workers,
    }
    with open(os.path.join(FIX, "mix_8dev_expected.json"), "w") as fh:
        json.dump(expected, fh, indent=1, sort_keys=True)
        fh.write("\n")
    print(json.dumps(expected, indent=1, sort_keys=True))


if __name__ == "__main__":
    main()
