"""The control fails: the reference put in the program's place with its
field in a lower precision (bfloat16, the step below the configurations'
float32 at the default precision, whose chip readings set the `loss_gap`
limits; and float8 e4m3) reads `correct` false under each cell's limits,
as do the faults planted in it (`reference.FAULTS`); a state left
unchanged reads 1. At smoke size on the CPU; `bench/calibrate.py` reads the
same at each cell's own size on the chip. And the sound reference itself
is pinned: at smoke size it gives what it gave before the model-specific
code moved into the families (`fixtures/smoke_reference.npz`, written by
the reference of that tree from the same seed and rows)."""
import functools
import json
import os

import numpy as np
import pytest

import benchkit

import check
import run
from reference import FAULTS, run_reference

CELLS = [w["name"] for w in json.load(open(
    f"{benchkit.REPO}/BENCHMARK.json"))["workloads"]] + ["dcgan32.q8.b64.w4"]


def _spec(cell):
    return benchkit.smoke(benchkit.resolve(cell))


@functools.lru_cache(maxsize=None)
def _sound(cell):
    """The cell's rows and its sound reference, read once per cell."""
    spec = _spec(cell)
    batches = run.reference_batches(spec, benchkit.SEED)
    return batches, _reference(spec, batches)


def _reference(spec, batches, **kw):
    traffic = spec["traffic"]
    return run_reference(spec["family"], spec["config"], traffic,
                         benchkit.SEED, batches, traffic["workers"], **kw)


def _numbers(cell, **kw):
    batches, ref = _sound(cell)
    return check.readings(_reference(_spec(cell), batches, **kw), ref), ref


def _correct(reads, limits):
    return check.judge(check.compared(reads, limits), limits)[0]


def _faults(spec):
    return [f for f in FAULTS
            if f != "no_exchange" or spec["traffic"]["workers"] > 1]


FIXTURE = os.path.join(benchkit.HERE, "fixtures", "smoke_reference.npz")


@pytest.mark.parametrize("cell", ["dcgan32.q8.b64", "dcgan32.q8.b64.w4",
                                  "dcgan32.exact.b64.w4"])
def test_smoke_reference_pinned(cell):
    """Losses, loss scales, the first field's leaf norms and the weights
    after three steps equal the fixture's float32 for float32: the one-chip
    exchange, two_phase and the exact mean. (At smoke size dcgan32-dim128
    is dcgan32.)"""
    _, ref = _sound(cell)
    fx = np.load(FIXTURE)
    for k in ("losses", "scales", "g1_norms"):
        assert np.array_equal(np.float32(ref[k]),
                              np.float32(fx[f"{cell}/{k}"])), k
    assert len(ref["w3"]) == 16
    for i, w in enumerate(ref["w3"]):
        assert w.dtype == np.float32
        assert np.array_equal(w, fx[f"{cell}/w3/{i}"]), i


@pytest.mark.parametrize("cell", CELLS)
def test_control_and_faults_fail(cell):
    spec = _spec(cell)
    limits = spec["limits"]
    nums, ref = _numbers(cell, control="float8_e4m3fn")
    assert not _correct(nums, limits), nums
    for f in _faults(spec):
        nums, _ = _numbers(cell, fault=f)
        assert not _correct(nums, limits), (f, nums)
    unchanged = {"losses": ref["losses"],
                 "q1": [np.zeros_like(x) for x in ref["q1"]],
                 "w0": ref["w0"], "w3": ref["w0"]}
    nums = check.readings(unchanged, ref)
    assert nums["update1_gap"] == 1.0 and nums["change3_gap"] == 1.0
    assert nums["change3_dir"] == pytest.approx(1.0)


@pytest.mark.parametrize("cell", CELLS)
def test_bfloat16_control_fails(cell):
    nums, _ = _numbers(cell, control="bfloat16")
    assert not _correct(nums, _spec(cell)["limits"]), nums


def test_omd_faults_read_apart():
    """The lookahead dropped and the apply's sign flipped, on the one-chip
    cell: the first is seen in the loss from step 1 on, through the seeded
    optimistic term; the second reads about 2 on the change after three
    steps projected on the reference's."""
    cell = "dcgan32.q8.b64"
    nums, _ = _numbers(cell, fault="no_lookahead")
    assert nums["loss_gap"] > 10 * _spec(cell)["limits"]["loss_gap"], nums
    nums, _ = _numbers(cell, fault="sign_flip")
    assert nums["change3_dir"] > 1.9, nums
