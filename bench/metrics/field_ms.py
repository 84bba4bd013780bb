"""field_ms: device time per step of the ops traced under the
`repro.obs/field` scope (the G and D forward and backward), averaged over
the chips."""
import tracefmt as T


def read(ctx):
    return T.scope_ms(ctx["trace"], "repro.obs/field")
