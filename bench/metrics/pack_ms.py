"""pack_ms: device time per step of the ops traced under the
`repro.obs/pack` scope (inside `repro.obs/exchange`: packing the message
and residual leaves into flat buckets, and unpacking the results),
averaged over the chips. An op whose metadata also names
`repro.obs/compress` (XLA merges the relayout of a packed bucket with the
kernel operand's reshape into one op that carries both names) counts
under compress_ms alone, so that the two never count one op twice."""
import tracefmt as T

PACK, COMPRESS = "repro.obs/pack", "repro.obs/compress"


def read(ctx):
    tr = ctx["trace"]
    per_chip, found = [], False
    for c in T.chips_seen(tr):
        iv = T.chip_ops(tr, c, lambda h: PACK in h.get("op_name", "")
                        and COMPRESS not in h.get("op_name", ""))
        found = found or bool(iv)
        per_chip.append(T.length(iv))
    if not found or not tr["steps"]:
        return None
    return sum(per_chip) / len(per_chip) / tr["steps"] / 1e6
