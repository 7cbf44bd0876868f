"""Device milliseconds per call of the jitted commit step, from the trace."""

KEY = "commit_step"


def read(ctx):
    if ctx.trace is None:
        return None
    us, n = ctx.trace.time_of(KEY)
    return us * 1e-3 / n if n else None
