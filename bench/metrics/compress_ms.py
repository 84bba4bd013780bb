"""compress_ms: device time per step of the ops traced under the
`repro.obs/compress` scope (inside `repro.obs/exchange`: the uniform
draws, the fused EF and int8 kernel or the jnp quantizer, the residual
and the decode; never a collective), averaged over the chips."""
import tracefmt as T


def read(ctx):
    return T.scope_ms(ctx["trace"], "repro.obs/compress")
