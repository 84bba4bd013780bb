"""Drift guard: the step the benchmark assembles is the launcher's step.

From the same seed and the same batches, `bench/program.py`'s step and the
one `repro.launch.train.run` builds must leave bit-identical parameters
after a few steps: on one device, and on four workers of a ("data",) mesh.
The benchmark makes the weights in one jitted call, whose fused
normal-times-scale rounds apart from the launcher's leaf-by-leaf init, so
the launcher here gets the same jitted init: what is guarded is the step.
"""
import json

import benchkit

STEPS = 4
SEED = 3

CODE = r"""
import json, sys
import jax, numpy as np
import benchkit, run
from program import build_program
from repro.launch import train

cell, steps, seed = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
spec = benchkit.resolve(cell)
fam, cfg = spec["family"], spec["config"]
# the launcher's --smoke shape of dcgan32 (`GANConfig.reduced`)
cfg["gan_config"].update(image_size=8, channels=1, latent_dim=16,
                         base_width=8, name="dcgan32-smoke")
t = spec["traffic"]
W, B = t["workers"], 8
t.update(batch_per_worker=B)
pool = fam.make_pool(cfg, seed, steps, W * B)

prog = build_program(fam, cfg, t, seed, W)
with prog.context():
    for i in range(steps):
        prog.step_once(pool[i])
ours = jax.device_get(jax.tree.leaves(prog.state.params))

import dataclasses
_build = train.build
train.build = lambda cfg: dataclasses.replace(
    _build(cfg), init=jax.jit(_build(cfg).init, static_argnames="max_seq"))
train.gan_batch_iterator = lambda s, b, cfg: iter(pool)
res = train.run(["--arch", "dcgan32", "--smoke", "--steps", str(steps),
                 "--batch", str(W * B), "--optimizer", "omd",
                 "--lr", repr(t["lr"]), "--seed", str(seed),
                 "--log-every", "1000"] + t["flags"])
theirs = jax.device_get(jax.tree.leaves(res.state.params))
same = all(np.array_equal(a, b) for a, b in zip(ours, theirs))
moved = any(not np.array_equal(a, b) for a, b in
            zip(ours, jax.device_get(jax.tree.leaves(
                build_program(fam, cfg, t, seed, W).state.params))))
print(json.dumps({"same": same, "moved": moved, "n": len(ours)}))
"""


def _check(cell, n_devices):
    out = benchkit.run_subprocess(
        CODE.replace("sys.argv[1], int(sys.argv[2]), int(sys.argv[3])",
                     f"{cell!r}, {STEPS}, {SEED}"), n_devices)
    res = json.loads(out.strip().splitlines()[-1])
    assert res == {"same": True, "moved": True, "n": 16}


def test_one_device_matches_the_launcher():
    _check("dcgan32.q8.b64", 1)


def test_four_workers_match_the_launcher():
    _check("dcgan32.q8.b64.w4", 4)
