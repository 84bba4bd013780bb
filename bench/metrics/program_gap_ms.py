"""program_gap_ms: per step, mean over the chips, the traced window's time
inside the step program's executions during which none of its ops runs
(`executions.gaps_ms`): idle that fewer, larger ops would remove."""
import executions as E


def read(ctx):
    gaps = E.gaps_ms(ctx["trace"])
    return gaps[0] if gaps else None
