"""dispatch_ms: host wall from calling the jitted step to its return (the
enqueue), mean per step, over an untraced window of the traced run."""


def read(ctx):
    d = ctx["dispatch_s"]
    return 1e3 * sum(d) / len(d) if d else None
