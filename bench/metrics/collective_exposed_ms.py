"""collective_exposed_ms: device time per step of the collective ops
(all-gather, all-to-all, all-reduce, reduce-scatter, collective-permute,
and async ops that run one) during which no other op runs on that chip,
averaged over the chips."""
import tracefmt as T


def read(ctx):
    tr = ctx["trace"]
    exposed, found = [], False
    for c in T.chips_seen(tr):
        coll = T.chip_ops(tr, c, lambda h: h.get("collective", False))
        found = found or bool(coll)
        other = T.chip_ops(tr, c, lambda h: not h.get("collective", False))
        exposed.append(T.length(T.subtract(coll, other)))
    if not found or not tr["steps"]:
        return None
    return sum(exposed) / len(exposed) / tr["steps"] / 1e6
