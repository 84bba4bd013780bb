"""launch_gap_ms: per step, mean over the chips, the traced window's time
outside every execution of the step program (`executions.gaps_ms`): the
device waits on the host to finish a step and enqueue the next."""
import executions as E


def read(ctx):
    gaps = E.gaps_ms(ctx["trace"])
    return gaps[1] if gaps else None
