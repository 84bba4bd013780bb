"""attention_ms: device time per step of the ops traced under the
`repro.obs/attention` scope (BigGAN's self-attention in G and D: its 1x1
convolutions, the two products, the softmax and the pooling, forward and
backward), averaged over the chips. None where no op carries the scope."""
import tracefmt as T


def read(ctx):
    return T.scope_ms(ctx["trace"], "repro.obs/attention")
