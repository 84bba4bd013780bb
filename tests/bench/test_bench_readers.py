"""Each per-layer reader on a compact trace built to the format, against
values worked out by hand; the HLO facts on an excerpt of a step compiled
on a TPU v5e."""
import json
import os

import pytest

import benchkit

import run
import tracefmt

FIXTURE = os.path.join(benchkit.HERE, "fixtures", "step_hlo_excerpt.txt")
US = 1000.0  # ns


def _trace():
    """Two chips, a 1 ms window holding 2 steps. Chip 0: field ops for
    300 us, an exchange op for 100 us (with a 10 us quantize call over an
    (8, 1024) tile inside it), a collective from 500 to 700 us half-hidden
    by a 50 us compute op. Chip 1: field 200 us, a collective 500-600 us
    with nothing beside it."""
    hlo = {
        "f1": {"op_name": "jit(step)/repro.obs/field/conv", "opcode": "fusion",
               "kernel": "", "shape": None, "collective": False},
        "x1": {"op_name": "jit(step)/repro.obs/exchange/sub",
               "opcode": "fusion", "kernel": "", "shape": None,
               "collective": False},
        "k1": {"op_name": "jit(step)/repro.obs/exchange/pallas_call",
               "opcode": "custom-call", "kernel": "_quantize_ef_kernel",
               "shape": [8, 1024], "collective": False,
               "spaces_in": [0, 0, 0], "spaces_out": [0, 0, 0]},
        "ag": {"op_name": "jit(step)/repro.obs/exchange/all_gather",
               "opcode": "all-gather-done", "kernel": "", "shape": None,
               "collective": True},
        "a1": {"op_name": "jit(step)/repro.obs/apply/sub", "opcode": "fusion",
               "kernel": "", "shape": None, "collective": False},
    }
    ops = [
        [0, "f1", 0.0, 300 * US],
        [0, "x1", 300 * US, 90 * US],
        [0, "k1", 390 * US, 10 * US],
        [0, "ag", 500 * US, 200 * US],
        [0, "a1", 600 * US, 50 * US],
        [1, "f1", 0.0, 200 * US],
        [1, "ag", 500 * US, 100 * US],
        [1, "f1", 2000 * US, 50 * US],     # after the window: left out
    ]
    host = [["bench/dispatch", 700 * US, 100 * US],
            ["bench/sync", 800 * US, 200 * US]]
    return {"window_ns": [0.0, 1000 * US], "steps": 2, "chips": 2,
            "ops": ops, "host": host, "hlo": hlo}


def _ctx():
    spec = benchkit.smoke(benchkit.resolve("dcgan32.q8.b64.w4"))
    peaks = json.load(open(os.path.join(benchkit.BENCH, "peaks.json")))
    return {"trace": _trace(), "config": spec["config"],
            "family": spec["family"], "traffic": spec["traffic"],
            "dispatch_s": [0.001, 0.003], "window_s": 0.001, "steps": 2,
            "chips": 2, "peaks": peaks["devices"]["TPU v5 lite"]}


def _read(name, ctx):
    return run.reader(os.path.join(benchkit.BENCH, "metrics"), name)(ctx)


def test_idle_share():
    # chip 0 busy 0-400 and 500-700 us: 600 us; chip 1 busy 200 + 100 us
    assert _read("device_idle_share", _ctx()) == pytest.approx(
        100 * (1 - (600 + 300) / 2 / 1000))


def test_scoped_device_time():
    ctx = _ctx()
    # field: (300 + 200) / 2 chips / 2 steps us
    assert _read("field_ms", ctx) == pytest.approx(0.125)
    # exchange: chip 0 90 + 10 + 200 us, chip 1 100 us
    assert _read("exchange_ms", ctx) == pytest.approx(
        (300 + 100) / 2 / 2 / 1000)


def test_collective_exposed():
    # chip 0: 200 us less the 50 us beside it; chip 1: 100 us
    assert _read("collective_exposed_ms", _ctx()) == pytest.approx(
        (150 + 100) / 2 / 2 / 1000)


def test_quantize_roofline():
    least = (8 * 1024 * 17 + 8 * 4) / 819e9
    assert _read("quantize_roofline", _ctx()) == pytest.approx(
        100 * least / 10e-6)


def test_mfu_and_dispatch():
    ctx = _ctx()
    per_step = ctx["family"].step_flops(ctx["config"], 8, 4)
    assert _read("mfu", ctx) == pytest.approx(
        100 * per_step * 2 / 0.001 / (2 * 197e12))
    assert _read("dispatch_ms", ctx) == pytest.approx(2.0)


def test_readers_find_nothing_to_read():
    ctx = _ctx()
    tr = ctx["trace"]
    tr["ops"] = [o for o in tr["ops"] if o[1] == "f1"]
    for name in ("collective_exposed_ms", "quantize_roofline",
                 "exchange_ms"):
        assert _read(name, ctx) is None
    ctx["trace"]["ops"] = []
    assert _read("device_idle_share", ctx) is None
    assert _read("field_ms", ctx) is None


def test_breakdown():
    b = run.breakdown(_trace())
    ops = dict(b["device_ops"])
    assert ops["f1 repro.obs/field/conv"] == pytest.approx(
        (300 + 200) * 1e-6 / 2)
    gaps = dict(b["idle_gaps"])
    # chip 0 idle 400-500 and 700-1000 us; chip 1 200-500, 600-1000 us
    assert gaps["bench/dispatch"] == pytest.approx((100 + 100) * 1e-6 / 2)
    assert gaps["bench/sync"] == pytest.approx((200 + 200) * 1e-6 / 2)
    assert gaps["no bench span"] == pytest.approx((100 + 300 + 100) * 1e-6
                                                  / 2)


def test_hlo_facts_of_a_chip_compile():
    info = tracefmt.hlo_info(open(FIXTURE).read())
    k = info["exchange.5"]
    assert k["kernel"] == "_quantize_ef_kernel" and k["shape"] == [512, 1024]
    # XLA placed every operand and result of this call in VMEM
    assert k["spaces_in"] == [1, 1, 1] and k["spaces_out"] == [1, 1, 1]
    assert "repro.obs/exchange" in k["op_name"] and not k["collective"]
    assert "repro.obs/field" in info["broadcast_maximum_fusion"]["op_name"]
    assert info["fusion.250"]["opcode"] == "fusion"
    assert info["all-gather-start.1"]["collective"]
    assert info["all-gather-done.1"]["collective"]


def test_interval_arithmetic():
    assert tracefmt.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert tracefmt.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2),
                                                               (3, 5)]
    assert tracefmt.length([(0, 2), (1, 4)]) == 4
