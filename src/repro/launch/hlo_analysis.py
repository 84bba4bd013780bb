"""While-loop-aware analysis of optimized (post-SPMD, per-device) HLO text.

XLA's `compiled.cost_analysis()` on CPU counts each while-loop *body once*,
which understates FLOPs/bytes/collectives for scan-over-layers models by
~num_layers (verified in EXPERIMENTS.md §Dry-run). This module re-walks the
HLO call graph with loop-trip multipliers:

  * computations are parsed from the text;
  * `while` ops contribute body+condition costs × trip count, where the
    trip count is recovered from the comparison constant in the condition
    computation (lax.scan lowers to `iv < constant`);
  * `fusion`/`call`/`conditional` recurse into their called computations
    (conditional branches counted once — upper bound of one branch);
  * per-instruction cost = result-shape bytes (traffic proxy) and, for
    collective ops, collective bytes by category;
  * dot/convolution FLOPs are NOT re-derived here (operand shapes are not
    printed in optimized HLO) — the roofline compute term instead uses the
    analytic MODEL_FLOPS counter (launch.dryrun.model_flops + attention
    terms), with raw cost_analysis FLOPs reported alongside.
"""
from __future__ import annotations

import re
from typing import Dict, List, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}
_TYPE_RE = re.compile(
    r"(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|f64|c64|c128|s4|u4"
    r"|f8e4m3fn|f8e5m2)\[([0-9,]*)\]")
_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")
_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->")
_COMMENT = re.compile(r"/\*.*?\*/")
# a layout after a shape: CPU `f32[8,4]{1,0}`, TPU `f32[64]{0:T(128)S(1)}`.
# Its tiling parens would otherwise end the result-type segment early.
_LAYOUT = re.compile(r"\]\{[^{}]*\}")
_CALLED = re.compile(
    r"(?:calls=|condition=|body=|to_apply=|branch_computations=\{)"
    r"\s*%?([\w.\-]+(?:\s*,\s*%?[\w.\-]+)*)")


def _shape_bytes(segment: str) -> int:
    total = 0
    for dt, dims in _TYPE_RE.findall(segment):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_computations(txt: str) -> Dict[str, List[str]]:
    comps: Dict[str, List[str]] = {}
    cur = None
    for line in txt.splitlines():
        stripped = _LAYOUT.sub("]", _COMMENT.sub("", line)).strip()
        if not stripped:
            continue
        if ("->" in stripped and "{" in stripped and "=" not in
                stripped.split("->")[0]):
            m = _COMP_HDR.match(stripped)
            if m:
                cur = m.group(1)
                comps[cur] = []
                continue
        if stripped.startswith("}"):
            # keep cur so stray ROOT lines don't crash; next header resets
            continue
        if cur is not None:
            comps[cur].append(stripped)
    return comps


def _constants(lines: List[str]) -> Dict[str, int]:
    out = {}
    for ln in lines:
        m = re.match(r"%?([\w.\-]+)\s*=\s*s(?:32|64)\[\]\s*constant\((\d+)\)",
                     ln)
        if m:
            out[m.group(1)] = int(m.group(2))
    return out


def _trip_count(cond_lines: List[str]) -> int:
    """lax.scan condition: compare(iv, const) direction=LT."""
    consts = _constants(cond_lines)
    for ln in cond_lines:
        if "compare(" in ln:
            args = re.search(r"compare\(([^)]*)\)", ln)
            if not args:
                continue
            for a in args.group(1).split(","):
                name = a.strip().lstrip("%")
                if name in consts:
                    return max(consts[name], 1)
    if consts:
        return max(consts.values())
    return 1


class HLOAnalysis:
    def __init__(self, txt: str):
        self.comps = parse_computations(txt)
        self.entry = None
        for line in txt.splitlines():
            if line.strip().startswith("ENTRY"):
                m = _COMP_HDR.match(line.strip()[len("ENTRY"):].strip())
                if m:
                    self.entry = m.group(1)
        if self.entry is None and self.comps:
            self.entry = list(self.comps)[-1]
        self.collectives: Dict[str, dict] = {}
        self.traffic_bytes = 0.0
        self.while_trips: List[int] = []
        self._walk(self.entry, 1.0, set())

    def _walk(self, comp: str, mult: float, stack: frozenset):
        lines = self.comps.get(comp)
        if lines is None or comp in stack:
            return
        stack = set(stack) | {comp}
        for ln in lines:
            if "=" not in ln:
                continue
            lhs, rhs = ln.split("=", 1)
            op_m = re.match(r"\s*\(?[\w\[\],{}\s/*]*?\)?\s*([\w\-]+)\(",
                            rhs.strip())
            opname = op_m.group(1) if op_m else ""
            # the result-type segment: everything left of the op name.
            # `rhs.split("(")[0]` would truncate tuple-typed results
            # (async -start ops, multi-output fusions) at the tuple's
            # own paren and count zero bytes for them.
            if op_m:
                result_seg = rhs.strip()[:op_m.start(1)]
            else:
                result_seg = rhs.split("(")[0]
            # no-cost ops: data-movement bookkeeping and loop plumbing.
            # `fusion` IS counted (its result is the one real HBM write of
            # the whole fused chain) but NOT recursed into — fused
            # elementwise internals stay in registers/VMEM.
            free = opname in (
                "tuple", "get-tuple-element", "parameter", "constant",
                "while", "conditional", "call", "bitcast",
                "after-all", "opt-barrier",
            )
            result_bytes = _shape_bytes(lhs + "=" + result_seg)
            if not free:
                self.traffic_bytes += mult * result_bytes
            cm = re.search(r"\b(" + "|".join(_COLL_OPS) + r")(-start)?\(", rhs)
            if cm and "-done(" not in rhs:
                op = cm.group(1)
                rec = self.collectives.setdefault(
                    op, {"count": 0, "bytes": 0.0, "int8_bytes": 0.0})
                rec["count"] += mult
                entries = _TYPE_RE.findall(lhs + "=" + result_seg)
                if cm.group(2):
                    # async pair: the -start result is a tuple aliasing
                    # the operand(s) alongside the destination buffer(s),
                    # and the matching -done re-prints the destination.
                    # Count the destination half once here (the -done
                    # line is excluded above), not operand + destination.
                    entries = entries[len(entries) // 2:]
                for dt, dims in entries:
                    n = 1
                    for d in dims.split(","):
                        if d:
                            n *= int(d)
                    nb = n * _DTYPE_BYTES[dt]
                    rec["bytes"] += mult * nb
                    if dt in ("s8", "u8", "pred", "s4", "u4"):
                        rec["int8_bytes"] += mult * nb
            if "while(" in rhs:
                called = dict(
                    re.findall(r"(condition|body)=%?([\w.\-]+)", rhs))
                body = called.get("body")
                cond = called.get("condition")
                trips = _trip_count(self.comps.get(cond, [])) if cond else 1
                self.while_trips.append(trips)
                if body:
                    self._walk(body, mult * trips, frozenset(stack))
                if cond:
                    self._walk(cond, mult * trips, frozenset(stack))
            else:
                # recurse into real control flow only: fusion computations
                # and reduce to_apply bodies are VMEM/register-resident
                # (their single HBM write is the caller's result, counted
                # above); collectives never appear inside them.
                if opname == "call":
                    for m in re.finditer(r"to_apply=%?([\w.\-]+)", rhs):
                        self._walk(m.group(1), mult, frozenset(stack))
                bm = re.search(r"branch_computations=\{([^}]*)\}", rhs)
                if bm:
                    for b in bm.group(1).split(","):
                        self._walk(b.strip().lstrip("%"), mult,
                                   frozenset(stack))

    def summary(self) -> dict:
        return {
            "collectives": {
                k: {"count": round(v["count"], 1),
                    "bytes": int(v["bytes"]),
                    "int8_bytes": int(v["int8_bytes"])}
                for k, v in self.collectives.items()
            },
            "traffic_result_bytes": int(self.traffic_bytes),
            "while_trip_counts": sorted(set(self.while_trips), reverse=True),
        }


def analyze(txt: str) -> dict:
    return HLOAnalysis(txt).summary()


# --------------------------------------------------------------------------- #
# named-scope attribution (repro.obs spans → HLO op metadata)
# --------------------------------------------------------------------------- #
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_costs(txt: str, prefix: str = "repro.obs/") -> Dict[str, dict]:
    """Per-scope op counts and result bytes from HLO op metadata.

    `jax.named_scope(prefix + phase)` (obs.device_span) survives into the
    optimized HLO as ``metadata={op_name="...<prefix><phase>/..."}`` on
    every op traced under the scope — so a compiled step lowered with
    spans on can attribute its device-side cost (op count + result-shape
    bytes, the same HBM-traffic proxy `HLOAnalysis` uses) to the
    compress/exchange/apply phases without running a profiler. Fused ops
    carry the scope of their representative op; attribution is therefore
    a proxy, not a cycle count — good enough to rank phases and to feed
    the profile events' per-phase split (DESIGN.md §12.1).

    Returns {phase: {"ops": int, "bytes": int}} for every scope name
    found under `prefix` (the segment right after it)."""
    out: Dict[str, dict] = {}
    for line in txt.splitlines():
        m = _OP_NAME.search(line)
        if not m or prefix not in m.group(1):
            continue
        tail = m.group(1).split(prefix, 1)[1]
        phase = tail.split("/", 1)[0].split('"', 1)[0]
        if not phase:
            continue
        stripped = _LAYOUT.sub("]", _COMMENT.sub("", line)).strip()
        # the result type sits after `=` and before the op's paren:
        #   %name = f32[8,128]{1,0} fusion(...), metadata={op_name=...}
        if "=" in stripped:
            seg = stripped.split("=", 1)[1].split("(", 1)[0]
        else:
            seg = ""
        rec = out.setdefault(phase, {"ops": 0, "bytes": 0})
        rec["ops"] += 1
        rec["bytes"] += _shape_bytes(seg)
    return out
