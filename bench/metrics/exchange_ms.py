"""exchange_ms: device time per step of the ops traced under the
`repro.obs/exchange` scope (error feedback, the quantizer and the
collectives), averaged over the chips."""
import tracefmt as T


def read(ctx):
    return T.scope_ms(ctx["trace"], "repro.obs/exchange")
