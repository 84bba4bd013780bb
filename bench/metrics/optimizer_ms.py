"""optimizer_ms: device time per step of the ops traced under the
`repro.obs/lookahead` scope (the OMD extrapolation to w_{t-1/2}) or the
`repro.obs/apply` scope (the server update), averaged over the chips."""
import tracefmt as T

SCOPES = ("repro.obs/lookahead", "repro.obs/apply")


def read(ctx):
    tr = ctx["trace"]
    per_chip, found = [], False
    for c in T.chips_seen(tr):
        iv = T.chip_ops(tr, c, lambda h: any(
            s in h.get("op_name", "") for s in SCOPES))
        found = found or bool(iv)
        per_chip.append(T.length(iv))
    if not found or not tr["steps"]:
        return None
    return sum(per_chip) / len(per_chip) / tr["steps"] / 1e6
