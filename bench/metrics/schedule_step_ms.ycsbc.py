"""Device milliseconds per call of the jitted schedule step, from the trace."""

KEY = "schedule_step"


def read(ctx):
    if ctx.trace is None:
        return None
    us, n = ctx.trace.time_of(KEY)
    return us * 1e-3 / n if n else None
