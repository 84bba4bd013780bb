"""The readers that split the device's time per step: the scoped ones
(compress, pack, optimizer) and those that rebuild the step program's
executions from its ops (program_gap, launch_gap), and the clock check,
on compact traces built by hand."""
import os

import pytest

import benchkit

import executions as E
import run
import tracefmt as T

US = 1000.0  # ns

# the step program's instructions in schedule order, as the compiled HLO
# lists them: lookahead, field, pack, the quantize kernel, the rest of the
# exchange, apply
HLO = {
    "l1": "jit(step)/repro.obs/lookahead/sub",
    "f1": "jit(step)/repro.obs/field/conv",
    "p1": "jit(step)/repro.obs/exchange/repro.obs/pack/concatenate",
    "c1": "jit(step)/repro.obs/exchange/repro.obs/compress/pallas_call",
    "x1": "jit(step)/repro.obs/exchange/mul",
    "a1": "jit(step)/repro.obs/apply/sub",
}
# one execution, from its start (us): 130 us of ops, gaps 100-105 and
# 135-140, so 140 us long
SHAPE = [("l1", 0, 10), ("f1", 10, 70), ("p1", 85, 10), ("c1", 95, 20),
         ("x1", 120, 10), ("a1", 130, 10)]


def _execution(chip, t0):
    return [[chip, n, (t0 + s) * US, d * US] for n, s, d in SHAPE]


def _in_window(ops, w0=0.0, w1=1000 * US):
    return [o for o in ops if o[2] < w1 and o[2] + o[3] > w0]


def _trace(shift_us=0.0, chips=(0, 1)):
    """A 1 ms window holding 3 steps. Host: step k dispatches over
    [300k + 50, 300k + 70] us and syncs over [300k + 70, 300k + 245] us.
    Chip 0 runs the program at 65, 365 and 665 us (each inside its step's
    host interval: 15 us after the dispatch starts, ending 40 us before
    the sync does). Chip 1 runs
    it at -100 (only its tail in the window), 400 and 900 us (cut by the
    window's end). `shift_us` moves every device op."""
    ops = []
    if 0 in chips:
        for t0 in (65, 365, 665):
            ops += _execution(0, t0 + shift_us)
    if 1 in chips:
        for t0 in (-100, 400, 900):
            ops += _execution(1, t0 + shift_us)
    host = []
    for k in range(3):
        host += [["bench/dispatch", (300 * k + 50) * US, 20 * US],
                 ["bench/sync", (300 * k + 70) * US, 175 * US]]
    hlo = {n: {"op_name": name, "opcode": "fusion", "kernel": "",
               "shape": None, "collective": False}
           for n, name in HLO.items()}
    return {"window_ns": [0.0, 1000 * US], "steps": 3, "chips": len(chips),
            "ops": _in_window(ops), "host": host, "hlo": hlo}


def _read(name, tr):
    return run.reader(os.path.join(benchkit.BENCH, "metrics"), name)(
        {"trace": tr})


def test_executions_rebuilt_from_ops():
    ex = E.executions(_trace())
    assert ex[0] == [(65 * US, 205 * US), (365 * US, 505 * US),
                     (665 * US, 805 * US)]
    # chip 1: the first began before the window, the last ends after it
    assert ex[1] == [(-5 * US, 40 * US), (400 * US, 540 * US),
                     (900 * US, 1015 * US)]


def test_scoped_readers():
    tr = _trace()
    # chip 0: three whole executions; chip 1: the tail from c1 at 0 us
    # (15 us of it), one whole, and the head up to c1's 5 us
    assert _read("compress_ms", tr) == pytest.approx(
        (3 * 20 + (15 + 20 + 5)) / 2 / 3 / 1000)
    assert _read("pack_ms", tr) == pytest.approx(
        (3 * 10 + (10 + 10)) / 2 / 3 / 1000)
    assert _read("optimizer_ms", tr) == pytest.approx(
        (3 * 20 + (10 + 20 + 10)) / 2 / 3 / 1000)
    # the nested scopes lie inside exchange: compress + pack <= exchange
    exchange = _read("exchange_ms", tr)
    assert _read("compress_ms", tr) + _read("pack_ms", tr) <= exchange


def test_an_op_named_for_both_counts_under_compress():
    """XLA can merge a packed bucket's relayout with the kernel operand's
    reshape: the op then carries both names, joined by ';'."""
    tr = _trace()
    tr["hlo"]["p1"]["op_name"] = (HLO["p1"] + ";" + HLO["c1"].replace(
        "pallas_call", "reshape"))
    assert _read("pack_ms", tr) is None
    assert _read("compress_ms", tr) == pytest.approx(
        (3 * 30 + (15 + 30 + 15)) / 2 / 3 / 1000)
    assert _read("compress_ms", tr) <= _read("exchange_ms", tr)


def test_gap_readers():
    tr = _trace()
    # chip 0: 10 us of gaps in each of 3 executions; chip 1: 5 us in the
    # tail, 10 in the whole one, 5 in the head (980-985 us)
    assert _read("program_gap_ms", tr) == pytest.approx(
        (30 + 20) / 2 / 3 / 1000)
    # outside: chip 0 1000 - 3 x 140 us; chip 1 1000 - (40 + 140 + 100)
    assert _read("launch_gap_ms", tr) == pytest.approx(
        (580 + 720) / 2 / 3 / 1000)


@pytest.mark.parametrize("chip", [0, 1])
def test_busy_and_gaps_make_the_window(chip):
    tr = _trace(chips=(chip,))
    busy = T.length(T.chip_ops(tr, chip)) / tr["steps"] / 1e6
    window = (tr["window_ns"][1] - tr["window_ns"][0]) / tr["steps"] / 1e6
    inside, outside = E.gaps_ms(tr)
    assert busy + inside + outside == pytest.approx(window)


@pytest.mark.parametrize("shift_us,runs,outside", [
    (0.0, 3, 0), (-20.0, 3, 0), (-30.0, 3, 3), (50.0, 2, 2)])
def test_clock_check(shift_us, runs, outside):
    """Each run pairs with the dispatch that enqueued it and the sync that
    waited on it; a device clock early by 20 us reads within the 10 us
    slack (runs start 15 us after dispatch), by 30 us outside it, and one
    late by 50 us ends each run after its own sync, so that the next
    step's host interval pairs with it (the last run has none)."""
    chk = E.clock_check(_trace(shift_us, chips=(0,)))
    assert chk["runs"] == runs and chk["outside"] == outside
    lead = chk["start_after_dispatch_us"]
    lag = chk["sync_after_end_us"]
    if runs == 3:
        assert lead["min"] == pytest.approx(15 + shift_us)
        assert lead["median"] == pytest.approx(15 + shift_us)
        assert lag["min"] == pytest.approx(40 - shift_us)
    else:
        assert lead["min"] == pytest.approx(15 + shift_us - 300)
        assert lag["median"] == pytest.approx(40 - shift_us + 300)


def test_readers_find_nothing_to_read():
    # a program without the nested scopes (the parent's): compress and
    # pack read nothing, optimizer reads apply alone
    tr = _trace()
    for n in ("p1", "c1", "l1"):
        tr["hlo"][n]["op_name"] = "jit(step)/repro.obs/exchange/x"
    assert _read("compress_ms", tr) is None
    assert _read("pack_ms", tr) is None
    assert _read("optimizer_ms", tr) == pytest.approx(
        (3 * 10 + (10 + 10)) / 2 / 3 / 1000)
    # no ops: no executions, no gaps, no sync return
    tr["ops"] = []
    for name in ("compress_ms", "pack_ms", "optimizer_ms", "program_gap_ms",
                 "launch_gap_ms"):
        assert _read(name, tr) is None, name
    assert E.clock_check(tr) is None


def test_executions_refuse_a_bad_grouping():
    # an op the compiled program does not hold (another program ran)
    tr = _trace()
    tr["ops"].append([0, "other", 200 * US, 5 * US])
    assert E.executions(tr) is None
    assert _read("program_gap_ms", tr) is None
    # an instruction twice in one execution: the grouping is not one per
    # step
    tr = _trace()
    tr["ops"].append([0, "f1", 455 * US, 1 * US])
    assert E.executions(tr) is None
    # far fewer executions than steps
    tr = _trace()
    tr["steps"] = 10
    assert E.executions(tr) is None
