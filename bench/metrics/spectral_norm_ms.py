"""spectral_norm_ms: device time per step of the ops traced under the
`repro.obs/spectral_norm` scope (BigGAN's power iterations, each weight's
sigma and the division by it, and their backward pass), averaged over the
chips. None where no op carries the scope."""
import tracefmt as T


def read(ctx):
    return T.scope_ms(ctx["trace"], "repro.obs/spectral_norm")
