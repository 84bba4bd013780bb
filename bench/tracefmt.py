"""From a profiler trace and the compiled HLO to the compact trace the
per-layer readers take.

The compact trace is a plain dict, so a test can build one by hand:

    {"window_ns": [start, end],      # the traced window on the trace clock
     "steps": n,                     # training steps inside it
     "chips": c,
     "ops": [[chip, instr, start_ns, dur_ns], ...],   # 'XLA Ops' events
     "host": [[name, start_ns, dur_ns], ...],         # bench/* host spans
     "hlo": {instr: {"op_name": str, "opcode": str,
                     "collective": bool, "kernel": str, "shape": [r, c],
                     "spaces_in": [s, ...], "spaces_out": [s, ...]}}}

`ops` are the device's top-level operations on each chip, as the TPU
profiler lists them on its 'XLA Ops' line; `hlo` describes each by its HLO
instruction name, read from the compiled program's text: the named scope
it was traced under (`op_name`), whether it moves data between chips, and
for a Pallas kernel, the kernel's function name, its tile shape, and the
memory space of each operand and result as the compiler placed it (0 is
HBM; 1 is VMEM, where XLA keeps small arrays between operations).
"""
from __future__ import annotations

import base64
import glob
import os
import re

COLLECTIVE = re.compile(r"^(all-gather|all-reduce|all-to-all|reduce-scatter|"
                        r"collective-permute|ragged-all-to-all)")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%([^\s(]+)\s.*\{\s*$")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%([^\s,})]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_BODY = re.compile(r'"body":"([^"]+)"')
_KERNEL_SYM = re.compile(rb"\x00?([A-Za-z_][A-Za-z0-9_]*_kernel[A-Za-z0-9_]*)")
_SHAPE2 = re.compile(r"\[(\d+),(\d+)\]")
_ARRAY = re.compile(r"\b[a-z][a-z0-9]*\[[\d,]*\](\{[^}]*\})?")
_SPACE = re.compile(r"S\((\d+)\)")
_OPERAND = re.compile(r"%([^\s,()]+)")


def _spaces(type_str: str):
    """The memory space of each array of an HLO result type, in order."""
    out = []
    for m in _ARRAY.finditer(type_str):
        sp = _SPACE.search(m.group(1) or "")
        out.append(int(sp.group(1)) if sp else 0)
    return out


def _kernel_name(line: str) -> str:
    """The Pallas kernel's function name, from the serialized Mosaic body
    of a tpu_custom_call, or '' when none can be read."""
    m = _BODY.search(line)
    if not m:
        return ""
    try:
        body = base64.b64decode(m.group(1))
    except ValueError:
        return ""
    names = _KERNEL_SYM.findall(body)
    return names[0].decode() if names else ""


def hlo_info(hlo_text: str) -> dict:
    """{instruction name: facts} for every instruction of a compiled HLO
    module's text."""
    comp_ops, comp_calls, calls, info, spaces, args = {}, {}, {}, {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        mc = _COMP.match(line)
        if mc and " = " not in line.split("{")[0]:
            comp = mc.group(1)
            comp_ops.setdefault(comp, set())
            comp_calls.setdefault(comp, set())
            continue
        mi = _INSTR.match(line)
        if not mi:
            continue
        name, rest = mi.group(1), mi.group(2)
        mo = _OPCODE.search(" " + rest)
        opcode = mo.group(1) if mo else ""
        calls[name] = _CALLS.findall(rest)
        if comp is not None:
            comp_ops[comp].add(opcode)
            comp_calls[comp].update(calls[name])
        op_name = _OP_NAME.search(rest)
        kernel = ""
        if 'custom_call_target="tpu_custom_call"' in rest:
            kernel = _kernel_name(rest) or "tpu_custom_call"
        head, _, tail = rest.partition(" " + opcode + "(") if opcode \
            else (rest, "", "")
        spaces[name] = _spaces(head)
        shape = _SHAPE2.search(head) if opcode else None
        if kernel:
            args[name] = _OPERAND.findall(tail.split(")", 1)[0])
        info[name] = {"op_name": op_name.group(1) if op_name else "",
                      "opcode": opcode,
                      "kernel": kernel,
                      "shape": [int(shape.group(1)), int(shape.group(2))]
                      if shape else None}

    def has_collective(c, seen):
        if c in seen:
            return False
        seen.add(c)
        return (any(COLLECTIVE.match(op) for op in comp_ops.get(c, ()))
                or any(has_collective(sub, seen)
                       for sub in comp_calls.get(c, ())))

    for name, ops in args.items():
        info[name]["spaces_in"] = [sp for op in ops
                                   for sp in spaces.get(op, [0])]
        info[name]["spaces_out"] = spaces[name]
    for name, facts in info.items():
        facts["collective"] = bool(COLLECTIVE.match(facts["opcode"])) or any(
            has_collective(c, set()) for c in calls[name])
    return info


def xplane_path(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one profile in {trace_dir}, "
                           f"found {files}")
    return files[0]


def _instr(event_name: str) -> str:
    m = re.match(r"^%?([^\s=]+)", event_name)
    return m.group(1) if m else event_name


def compact(xplane: str, hlo_text: str, steps: int, chips: int) -> dict:
    """The compact trace of a profile whose traced window is the host span
    'bench/window'."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane)
    ops, host = [], []
    for plane in pd.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    ops.append([chip, _instr(e.name), float(e.start_ns),
                                float(e.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench/"):
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns)])
    windows = [h for h in host if h[0] == "bench/window"]
    if len(windows) != 1:
        raise RuntimeError(f"expected one bench/window span, got {windows}")
    _, w0, wd = windows[0]
    w1 = w0 + wd
    ops = [o for o in ops if o[2] < w1 and o[2] + o[3] > w0]
    host = [h for h in host if h[0] != "bench/window"
            and h[1] < w1 and h[1] + h[2] > w0]
    used = {o[1] for o in ops}
    info = {k: v for k, v in hlo_info(hlo_text).items() if k in used}
    return {"window_ns": [w0, w1], "steps": steps, "chips": chips,
            "ops": ops, "host": host, "hlo": info}


# --------------------------------------------------------------------------- #
# interval arithmetic shared by the readers
# --------------------------------------------------------------------------- #
def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def union(intervals):
    """Merged, sorted, non-overlapping intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def length(intervals) -> float:
    return sum(b - a for a, b in union(intervals))


def subtract(intervals, minus):
    """Parts of `intervals` not covered by `minus` (both any intervals)."""
    out = []
    cut = union(minus)
    for a, b in union(intervals):
        cur = a
        for c, d in cut:
            if d <= cur or c >= b:
                continue
            if c > cur:
                out.append((cur, c))
            cur = max(cur, d)
            if cur >= b:
                break
        if cur < b:
            out.append((cur, b))
    return out


def chip_ops(tr: dict, chip: int, pick=None):
    """[(start, end)] of chip's ops inside the window, optionally only those
    whose HLO facts satisfy `pick`."""
    w0, w1 = tr["window_ns"]
    hlo = tr["hlo"]
    return clip([(s, s + d) for c, name, s, d in tr["ops"]
                 if c == chip and (pick is None or pick(hlo.get(name, {})))],
                w0, w1)


def chips_seen(tr: dict):
    return sorted({o[0] for o in tr["ops"]}) or list(range(tr["chips"]))


def scope_ms(tr: dict, scope: str):
    """Device milliseconds per step of the ops traced under `scope`,
    averaged over the chips; None where no op carries the scope."""
    per_chip, found = [], False
    for c in chips_seen(tr):
        iv = chip_ops(tr, c, lambda h: scope in h.get("op_name", ""))
        found = found or bool(iv)
        per_chip.append(length(iv))
    if not found or not tr["steps"]:
        return None
    return sum(per_chip) / len(per_chip) / tr["steps"] / 1e6
