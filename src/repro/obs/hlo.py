"""Structural verification of the *compiled* step (DESIGN.md §12.2).

`comm.CommLedger` models what the exchange should move; this module
checks what the compiled program actually lowers. Built on
`launch.hlo_analysis` (the while-loop-aware optimized-HLO walker), it is
pure text analysis — runnable on host CI devices, no hardware profiler:

* `compiled_text(jitfn, *args)` — lower + compile to optimized
  (post-SPMD, per-device) HLO text.
* `collective_summary(txt)` — per-category collective op counts and
  result bytes (all-reduce / reduce-scatter / all-gather / ...).
* `byte_gap(txt, ledger)` — the measured-vs-modeled byte gap: HLO
  collective result bytes against the ledger's analytic per-step wire
  and carried bytes (per bucket rows included). The ledger's transport
  accounting bills a ring all-reduce at 2·(W−1)/W × payload
  (send+receive); an HLO collective's *result* materializes the payload
  once — `modeled_result_bytes` divides the transport factor back out
  so the two sides are commensurable.
* `check_schedule_structure(...)` — schedule-shaped assertions: an
  exchange step lowers all-reduce-class collectives; a `local_k`
  mid-round step lowers NO gradient-payload collective (nothing close
  to the bucket payload on the wire between rounds); `delayed(τ)`
  carries the τ-deep pending ring through the step's loop state (ring
  parameters visible in the entry signature); with ``overlap=True`` the
  exchange collectives are additionally DAG-independent of the field
  compute ("collective N overlaps compute region R", DESIGN.md §13).
* `check_fsdp_structure(...)` — ZeRO-shaped assertions: the exchange
  step lowers reduce-scatter/all-to-all + all-gather (not whole-payload
  all-reduce); with ``compressed=True`` the wire payload is int8.
* `exchange_field_independence(txt)` — the overlap invariant on any
  backend: no exchange-scoped collective transitively consumes a
  field-scoped op, so the scheduler is FREE to run wire and compute
  concurrently. Pure dataflow, works on XLA:CPU (which lowers sync
  collectives).
* `async_collective_pairs(txt)` — on backends whose scheduler has
  already committed to overlap (GPU/TPU with async collectives +
  latency hiding), the -start/-done pairs and the non-trivial compute
  scheduled between them.

The live checks need a multi-device lowering (collectives only appear
when W > 1); CI runs them on 8 forced host devices, while the committed
HLO fixture (tests/fixtures/) keeps the extraction logic covered on
every tier.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional

from repro.core.exchange import transport_factor
from repro.launch.hlo_analysis import (HLOAnalysis, _COLL_OPS, _OP_NAME,
                                       _TYPE_RE, parse_computations)

# collectives that implement a gradient averaging step ("all-reduce
# class"): a plain all-reduce, or its decomposed reduce-scatter +
# all-gather pair (two_phase), all count as exchange structure.
ALL_REDUCE_CLASS = ("all-reduce", "reduce-scatter", "all-gather")


# --------------------------------------------------------------------------- #
def compiled_text(jitfn, *args) -> str:
    """Optimized (post-SPMD, per-device) HLO text of a jitted callable
    on the given (possibly abstract) arguments."""
    return jitfn.lower(*args).compile().as_text()


_FRAME_TABLES = re.compile(
    r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*\n?",
    re.M)
_LOCATION = re.compile(
    r'(?:,? ?source_file="[^"]*"|,? ?source_line=\d+|,? ?stack_frame_id=\d+)')


def strip_locations(txt: str) -> str:
    """HLO text without its Python source locations (the stack-frame
    tables and the per-op source_file/source_line/stack_frame_id
    metadata), which depend on the checkout path and the calling script.
    Everything else — ops, shapes, op_name scopes — is kept."""
    return _LOCATION.sub("", _FRAME_TABLES.sub("", txt))


def collective_summary(txt: str) -> dict:
    """{category: {count, bytes, int8_bytes}} from optimized HLO text
    (loop-trip-corrected — a collective inside a scanned body counts
    once per trip)."""
    return HLOAnalysis(txt).summary()["collectives"]


def _class_totals(colls: dict) -> dict:
    ops = sum(v["count"] for k, v in colls.items() if k in ALL_REDUCE_CLASS)
    byts = sum(v["bytes"] for k, v in colls.items() if k in ALL_REDUCE_CLASS)
    i8 = sum(v["int8_bytes"] for k, v in colls.items()
             if k in ALL_REDUCE_CLASS)
    return {"ops": ops, "bytes": byts, "int8_bytes": i8}


# --------------------------------------------------------------------------- #
def byte_gap(txt: str, ledger, participants: Optional[int] = None) -> dict:
    """Measured-vs-modeled bytes: what the compiled step's collectives
    materialize vs what the `CommLedger` bills one exchange round at.

    Returns a report dict; ``gap_ratio`` is measured / modeled_result − 1
    (≈ 0 when the compiled wire format matches the carried-bytes model;
    positive = the program moves more than modeled)."""
    colls = collective_summary(txt)
    measured = float(sum(v["bytes"] for v in colls.values()))
    wire, carried = ledger.round_bytes(participants)
    transport = transport_factor(max(ledger.n_workers, 2))
    modeled_result = carried / transport if transport else carried
    return {
        "hlo_collectives": colls,
        "hlo_bytes": measured,
        "hlo_int8_bytes": float(sum(v["int8_bytes"]
                                    for v in colls.values())),
        "modeled_wire_bytes": wire,
        "modeled_carried_bytes": carried,
        "modeled_result_bytes": modeled_result,
        "gap_ratio": (measured / modeled_result - 1.0
                      if modeled_result else None),
        "per_bucket": ledger.per_bucket(participants),
    }


# --------------------------------------------------------------------------- #
# schedule-shaped structure
# --------------------------------------------------------------------------- #
_PARAM_LINE = re.compile(r"=\s*[\w\[\],{}\s/*]*?parameter\(\d+\)")


def entry_parameter_shapes(txt: str) -> List[tuple]:
    """Dim tuples of every ENTRY-computation parameter (the step's
    carried state + inputs as the compiled program sees them)."""
    entry_started = False
    shapes: List[tuple] = []
    for line in txt.splitlines():
        s = line.strip()
        if s.startswith("ENTRY"):
            entry_started = True
            continue
        if not entry_started:
            continue
        if s.startswith("}"):
            break
        if "parameter(" not in s or not _PARAM_LINE.search(s):
            continue
        lhs = s.split("parameter(", 1)[0]
        for _, dims in _TYPE_RE.findall(lhs):
            shapes.append(tuple(int(d) for d in dims.split(",") if d))
    return shapes


def ring_parameters(txt: str, tau: int) -> List[tuple]:
    """Entry parameters that look like τ-deep pending-ring slots: a dim
    equal to τ in the first two axes of a ≥2-D shape (the per-device
    ring is (τ, *leaf) or (W_local, τ, *leaf) depending on sharding)."""
    if tau < 2:
        return []
    out = []
    for shp in entry_parameter_shapes(txt):
        if len(shp) >= 2 and tau in shp[:2]:
            out.append(shp)
    return out


# --------------------------------------------------------------------------- #
# overlap structure (DESIGN.md §13)
# --------------------------------------------------------------------------- #
# ops that are pure data plumbing: compute "between" an async start and
# its done must be more than these to count as hidden work
_FREE_OPS = frozenset((
    "tuple", "get-tuple-element", "parameter", "constant", "bitcast",
    "after-all", "opt-barrier", "copy", "partition-id", "replica-id",
))
_INSTR = re.compile(r"^(?:ROOT\s+)?%?([\w.\-]+)\s*=")
# the result type left of the op name may be a parenthesized tuple
# (async -start ops, multi-output fusions) — skip it explicitly
_OPNAME_OF = re.compile(
    r"=\s*(?:\([^)]*\)\s*)?[\w\[\],{}\s/*]*?([\w\-]+)\(")
_COLL_RE = re.compile(r"\b(" + "|".join(_COLL_OPS) + r")(-start)?\(")


def _instr_table(lines: List[str]):
    """[(name, opname, operand-names, scope-op_name)] for one computation,
    in program order. Operands are the %tokens of the call argument list
    (metadata and computation references stripped)."""
    out = []
    for ln in lines:
        m = _INSTR.match(ln)
        if m is None or "=" not in ln:
            continue
        name = m.group(1)
        rhs = ln.split("=", 1)[1]
        om = _OPNAME_OF.search(ln)
        opname = om.group(1) if om else ""
        meta = _OP_NAME.search(ln)
        body = rhs.split(", metadata=")[0]
        # computation references are attributes, not dataflow operands
        body = re.sub(r"(?:calls|to_apply|condition|body)=%?[\w.\-]+", "",
                      body)
        body = re.sub(r"branch_computations=\{[^}]*\}", "", body)
        ops = re.findall(r"%([\w.\-]+)", body)
        out.append((name, opname, ops, meta.group(1) if meta else ""))
    return out


def async_collective_pairs(txt: str) -> dict:
    """Async -start/-done pairing evidence from optimized HLO.

    Backends that lower async collectives (GPU/TPU with the
    latency-hiding scheduler; see launch.mesh.enable_overlap_flags)
    print each overlapped collective as a `<op>-start` whose result a
    later `<op>-done` consumes; everything scheduled between the pair
    runs concurrently with the wire transfer. Returns per-pair non-free
    op counts; XLA:CPU (sync collectives only) legitimately reports
    ``pairs == 0`` — use `exchange_field_independence` for the
    backend-agnostic overlap invariant."""
    pairs = []
    unmatched = 0
    for comp, lines in parse_computations(txt).items():
        tab = _instr_table(lines)
        for i, (name, opname, _, _) in enumerate(tab):
            if not opname.endswith("-start") or not any(
                    opname == c + "-start" for c in _COLL_OPS):
                continue
            done_idx = None
            for j in range(i + 1, len(tab)):
                if tab[j][1] == opname[:-len("-start")] + "-done" and \
                        name in tab[j][2]:
                    done_idx = j
                    break
            if done_idx is None:
                unmatched += 1
                continue
            between = sum(1 for k in range(i + 1, done_idx)
                          if tab[k][1] not in _FREE_OPS)
            pairs.append({"computation": comp, "op": opname[:-6],
                          "start": name, "compute_between": between})
    return {
        "pairs": len(pairs),
        "unmatched_starts": unmatched,
        "min_compute_between": (min(p["compute_between"] for p in pairs)
                                if pairs else None),
        "detail": pairs,
    }


def exchange_field_independence(txt: str,
                                prefix: str = "repro.obs/") -> dict:
    """The backend-agnostic overlap invariant: every collective carrying
    the `repro.obs/exchange` scope must be DAG-independent of all
    `repro.obs/field`-scoped ops — its transitive operand closure inside
    its computation touches no field op. That is precisely the property
    that lets a latency-hiding scheduler run the wire transfer during
    the field compute; a blocking lowering whose message depends on this
    round's gradients (every_step/local_k) fails it by construction.

    Needs a lowering with spans on (`Observability(spans=True)`) so the
    scope metadata survives into the HLO; reports
    ``spans_present=False`` otherwise. Works on XLA:CPU, where async
    -start/-done pairs never appear but the dataflow freedom is the
    same."""
    exch_tag = prefix + "exchange"
    field_tag = prefix + "field"
    n_exch_colls = 0
    tainted: List[str] = []
    spans_present = False
    for comp, lines in parse_computations(txt).items():
        tab = _instr_table(lines)
        if not any(t[3] for t in tab):
            continue
        by_name = {t[0]: t for t in tab}
        if any(exch_tag in t[3] or field_tag in t[3] for t in tab):
            spans_present = True
        for name, opname, _, scope in tab:
            if exch_tag not in scope or not _COLL_RE.search(" " + opname
                                                           + "("):
                continue
            n_exch_colls += 1
            seen = set()
            stack = [name]
            hit = None
            while stack:
                cur = stack.pop()
                if cur in seen:
                    continue
                seen.add(cur)
                t = by_name.get(cur)
                if t is None:
                    continue
                if field_tag in t[3]:
                    hit = cur
                    break
                stack.extend(t[2])
            if hit is not None:
                tainted.append(f"{comp}::{name} depends on field op {hit}")
    return {
        "spans_present": spans_present,
        "exchange_collectives": n_exch_colls,
        "tainted": tainted,
        "ok": spans_present and n_exch_colls > 0 and not tainted,
    }


def check_schedule_structure(schedule, exchange_txt: str,
                             midround_txt: Optional[str] = None,
                             n_param_leaves: Optional[int] = None,
                             overlap: bool = False) -> dict:
    """Schedule-shaped assertions over compiled HLO text.

    ``schedule`` is a `repro.strategy.Schedule` (kind/k/tau);
    ``exchange_txt`` the optimized HLO of the do_exchange=True step
    variant, ``midround_txt`` (local_k only) the do_exchange=False
    variant. ``overlap=True`` (delayed × ExchangePlan.overlap) adds the
    "collective N overlaps compute region R" checks: the exchange
    collectives must be DAG-independent of the field compute
    (`exchange_field_independence`, any backend), and when the backend
    emitted async -start/-done pairs they must be matched with
    non-trivial compute between them (`async_collective_pairs`).
    Returns {"ok": bool, "violations": [...], ...evidence};
    `assert_schedule_structure` raises on violations."""
    violations: List[str] = []
    ex_colls = collective_summary(exchange_txt)
    ex_cls = _class_totals(ex_colls)
    report: Dict[str, object] = {
        "schedule": f"{schedule.kind}(k={schedule.k},tau={schedule.tau})",
        "exchange_collectives": ex_colls,
        "exchange_class_totals": ex_cls,
    }

    # every schedule's exchange step moves the message through at least
    # one all-reduce-class collective
    if ex_cls["ops"] < 1:
        violations.append(
            f"exchange step lowers no all-reduce-class collective "
            f"(got {sorted(ex_colls)})")

    # every_step needs nothing beyond the collective presence above:
    # every compiled step IS the exchange step. (A negative "no ring
    # state" probe is not reliable — small data dims collide with small
    # τ values in the shape scan.)
    if schedule.kind == "local_k":
        if midround_txt is None:
            violations.append(
                "local_k structure check needs the do_exchange=False "
                "(mid-round) variant's HLO")
        else:
            mid_colls = collective_summary(midround_txt)
            mid_cls = _class_totals(mid_colls)
            report["midround_collectives"] = mid_colls
            report["midround_class_totals"] = mid_cls
            # mid-round steps accumulate locally: no gradient payload on
            # the wire. Scalar metric reductions (loss/grad_norm psums)
            # are allowed; the payload-class bytes must collapse.
            if mid_cls["int8_bytes"] > 0:
                violations.append(
                    f"mid-round step moves quantized payload "
                    f"({mid_cls['int8_bytes']:.0f} int8 bytes)")
            if ex_cls["bytes"] and \
                    mid_cls["bytes"] >= 0.5 * ex_cls["bytes"]:
                violations.append(
                    f"mid-round collective bytes "
                    f"({mid_cls['bytes']:.0f}) not < half the exchange "
                    f"step's ({ex_cls['bytes']:.0f}) — the accumulator "
                    f"is leaking onto the wire between rounds")
    elif schedule.kind == "delayed":
        if schedule.tau >= 2:
            rings = ring_parameters(exchange_txt, schedule.tau)
            report["ring_parameters"] = rings
            need = n_param_leaves or 1
            if len(rings) < need:
                violations.append(
                    f"delayed(tau={schedule.tau}) carries "
                    f"{len(rings)} tau-deep ring parameter(s) through "
                    f"loop state, expected >= {need}")

    if overlap:
        if schedule.kind != "delayed":
            violations.append(
                f"overlap structure is only defined for the delayed "
                f"schedule, not {schedule.kind!r}")
        else:
            indep = exchange_field_independence(exchange_txt)
            report["overlap_independence"] = indep
            if not indep["spans_present"]:
                violations.append(
                    "overlap check needs a lowering with spans on "
                    "(Observability(spans=True)) so exchange/field scope "
                    "metadata survives into the HLO")
            elif indep["exchange_collectives"] < 1:
                violations.append(
                    "overlap step lowers no exchange-scoped collective")
            elif indep["tainted"]:
                violations.append(
                    "exchange collective(s) depend on this round's field "
                    "compute (overlap impossible): "
                    + "; ".join(indep["tainted"][:3]))
            pairs = async_collective_pairs(exchange_txt)
            report["async_pairs"] = pairs
            # async -start/-done only exists where the backend scheduler
            # committed to overlap (GPU/TPU); XLA:CPU lowers sync
            # collectives, so pairs==0 there is reported, not violated —
            # the independence check above is the CPU-tier guarantee.
            if pairs["pairs"] > 0:
                if pairs["unmatched_starts"]:
                    violations.append(
                        f"{pairs['unmatched_starts']} async collective "
                        f"start(s) without a matching -done")
                if (pairs["min_compute_between"] or 0) < 1:
                    violations.append(
                        "async collective pair(s) with no compute "
                        "scheduled between start and done — the wire "
                        "time is not being hidden")
    report["ok"] = not violations
    report["violations"] = violations
    return report


def assert_schedule_structure(schedule, exchange_txt: str,
                              midround_txt: Optional[str] = None,
                              n_param_leaves: Optional[int] = None,
                              overlap: bool = False) -> dict:
    report = check_schedule_structure(schedule, exchange_txt, midround_txt,
                                      n_param_leaves, overlap=overlap)
    if not report["ok"]:
        raise AssertionError(
            f"schedule structure violated for {report['schedule']}: "
            + "; ".join(report["violations"]))
    return report


# --------------------------------------------------------------------------- #
def check_fsdp_structure(exchange_txt: str,
                         compressed: bool = False) -> dict:
    """FSDP-shaped assertions over the compiled exchange step's HLO
    (DESIGN.md §15.4).

    A ZeRO-style step must lower a *scatter* collective (reduce-scatter
    for the exact path, all-to-all for the quantized two_phase path) to
    move each worker's shard in, and an all-gather to broadcast the
    shard update (zero-2) or the updated shard params (zero-3) back
    out. It must NOT fall back to whole-payload all-reduce: the
    all-reduce bytes that remain should be scalar metrics (loss,
    grad_norm psums), small next to the scatter/gather payload. With
    ``compressed=True`` the wire payload must additionally be int8.
    Returns {"ok": bool, "violations": [...], ...evidence};
    `assert_fsdp_structure` raises on violations."""
    violations: List[str] = []
    colls = collective_summary(exchange_txt)

    def cat(name):
        return colls.get(name, {"count": 0, "bytes": 0, "int8_bytes": 0})

    scatter_ops = cat("reduce-scatter")["count"] + cat("all-to-all")["count"]
    scatter_bytes = cat("reduce-scatter")["bytes"] + cat("all-to-all")["bytes"]
    gather = cat("all-gather")
    ar = cat("all-reduce")
    payload_bytes = scatter_bytes + gather["bytes"]
    report: Dict[str, object] = {
        "collectives": colls,
        "scatter_ops": scatter_ops,
        "scatter_bytes": scatter_bytes,
        "all_gather_ops": gather["count"],
        "all_gather_bytes": gather["bytes"],
        "all_reduce_bytes": ar["bytes"],
    }

    if scatter_ops < 1:
        violations.append(
            f"fsdp exchange step lowers no scatter collective "
            f"(reduce-scatter or all-to-all); got {sorted(colls)}")
    if gather["count"] < 1:
        violations.append(
            "fsdp exchange step lowers no all-gather (the shard "
            "update/params never return to the other workers)")
    # whole-payload all-reduce means the sharded path silently degraded
    # to replicated DDP; scalar metric psums are a few bytes each.
    if payload_bytes and ar["bytes"] >= 0.5 * payload_bytes:
        violations.append(
            f"all-reduce bytes ({ar['bytes']:.0f}) not < half the "
            f"scatter+gather payload ({payload_bytes:.0f}) — the fsdp "
            f"step is moving whole-payload all-reduces")
    if compressed:
        i8 = sum(v["int8_bytes"] for v in colls.values())
        report["int8_bytes"] = i8
        if i8 <= 0:
            violations.append(
                "compressed fsdp step moves no int8 payload on the wire")
    report["ok"] = not violations
    report["violations"] = violations
    return report


def assert_fsdp_structure(exchange_txt: str, compressed: bool = False) -> dict:
    report = check_fsdp_structure(exchange_txt, compressed=compressed)
    if not report["ok"]:
        raise AssertionError("fsdp structure violated: "
                             + "; ".join(report["violations"]))
    return report
