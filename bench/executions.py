"""The step program's executions on each chip, rebuilt from the compact
trace, and the host interval that enqueued and awaited each.

The compact trace (`tracefmt.compact`) keeps the device's ops, not the
runtime's record of each execution of the program, so an execution is
rebuilt from its ops: the program runs the same schedule every step, the
compiled HLO lists the entry computation in that order, and `hlo` keeps
the text's order. On each chip the op of the instruction that comes first
in the schedule opens an execution, which runs from that op's start to the
end of the last op before the next one. The grouping is checked before it
is used: every execution that lies wholly in the window holds each
instruction seen exactly once, and there are about as many as steps. Where
the check fails (ops of another program, a repeated instruction, lost
events) nothing is rebuilt, and the readers that need executions read
nothing. On a TPU v5e the executions rebuilt so start 0.8 us after, and
end 4.2 us before, the runtime's own record of them (the TPU plane's
'XLA Modules' line), and are as many.
"""
from __future__ import annotations

import statistics

import tracefmt as T

CLOCK_SLACK_NS = 10_000.0   # 10 us: the clock check's tolerance


def _groups(ops, first):
    """`ops` ([(start, end, instr)], sorted) split before each `first`."""
    cuts = [i for i, (_, _, name) in enumerate(ops) if name == first]
    bounds = ([0] if cuts[0] else []) + cuts + [len(ops)]
    return [ops[a:b] for a, b in zip(bounds, bounds[1:])]


def executions(tr: dict):
    """{chip: [(start_ns, end_ns), ...]} of the step program's executions
    that overlap the traced window, in order, or None where they cannot be
    rebuilt (see the module's doc). An execution cut by the window's edge
    is stretched to that edge: it began before the window, or ends after
    it."""
    order = {name: i for i, name in enumerate(tr["hlo"])}
    w0, w1 = tr["window_ns"]
    out = {}
    for c in T.chips_seen(tr):
        ops = sorted((s, s + d, name) for cc, name, s, d in tr["ops"]
                     if cc == c)
        seen = {name for _, _, name in ops}
        if not seen or not seen <= set(order):
            return None
        first = min(seen, key=order.__getitem__)
        groups = _groups(ops, first)
        whole = [g for g in groups[:-1] if g[0][2] == first]
        if (not whole or abs(len(groups) - tr["steps"]) > 2
                or any(len(g) > len(seen) for g in groups)
                or any(len(g) != len(seen) or {n for _, _, n in g} != seen
                       for g in whole)):
            return None
        runs = []
        for g in groups:
            start, end = g[0][0], max(e for _, e, _ in g)
            if g[0][2] != first:            # began before the window
                start = min(start, w0)
            if g is groups[-1] and len(g) < len(seen):   # ends after it
                end = max(end, w1)
            runs.append((start, end))
        out[c] = runs
    return out


def _host(tr: dict, name: str):
    return sorted((s, s + d) for n, s, d in tr["host"] if n == name)


def _awaiting_sync(syncs, end):
    """The sync span that waited on an execution ending at `end`: the one
    `end` falls in, else the first to end after it; None if none does."""
    for a, b in syncs:
        if a <= end <= b:
            return a, b
    later = [(a, b) for a, b in syncs if b > end]
    return min(later, key=lambda s: s[1]) if later else None


def host_pairs(tr: dict, execs: dict):
    """The clock check. For each execution that ends inside the window,
    the host interval that enqueued and awaited it: the sync span that
    waited on it (`_awaiting_sync`) and the last dispatch span to start
    before that sync. Returns [(chip, run start - dispatch start,
    sync end - run end)] in ns; on agreeing clocks both are >= 0."""
    syncs, disps = _host(tr, "bench/sync"), _host(tr, "bench/dispatch")
    w0, w1 = tr["window_ns"]
    out = []
    for c, runs in execs.items():
        for s, e in runs:
            if s <= w0 or e >= w1:      # cut by the window's edge
                continue
            sync = _awaiting_sync(syncs, e)
            if sync is None:
                continue
            before = [d for d in disps if d[0] <= sync[0]]
            if not before:
                continue
            out.append((c, s - before[-1][0], sync[1] - e))
    return out


def clock_check(tr: dict):
    """Summary of `host_pairs` in microseconds: the least and the median
    of (run start - dispatch start) and (sync end - run end), and how many
    runs lie outside their host interval by more than CLOCK_SLACK_NS."""
    execs = executions(tr)
    pairs = host_pairs(tr, execs) if execs else []
    if not pairs:
        return None
    lead = [p[1] / 1e3 for p in pairs]
    lag = [p[2] / 1e3 for p in pairs]
    return {"runs": len(pairs),
            "start_after_dispatch_us": {"min": min(lead),
                                        "median": statistics.median(lead)},
            "sync_after_end_us": {"min": min(lag),
                                  "median": statistics.median(lag)},
            "outside": sum(1 for a, b in zip(lead, lag)
                           if min(a, b) * 1e3 < -CLOCK_SLACK_NS)}


def gaps_ms(tr: dict):
    """(program_gap, launch_gap): milliseconds per step, mean over chips,
    of the window's time inside the program's executions when none of its
    ops runs, and of the window's time outside every execution; None
    where the executions cannot be rebuilt."""
    execs = executions(tr)
    if not execs or not tr["steps"]:
        return None
    w0, w1 = tr["window_ns"]
    inside, outside = [], []
    for c, runs in execs.items():
        runs = T.clip(runs, w0, w1)
        inside.append(T.length(T.subtract(runs, T.chip_ops(tr, c))))
        outside.append((w1 - w0) - T.length(runs))
    n = len(execs) * tr["steps"] * 1e6
    return sum(inside) / n, sum(outside) / n
