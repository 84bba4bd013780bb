"""device_idle_share: 1 - the union of device-op intervals over the traced
window, averaged over the chips, in percent."""
import tracefmt as T


def read(ctx):
    tr = ctx["trace"]
    if not tr["ops"]:
        return None
    w0, w1 = tr["window_ns"]
    busy = [T.length(T.chip_ops(tr, c)) for c in T.chips_seen(tr)]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (w1 - w0))
