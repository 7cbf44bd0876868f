"""Host milliseconds in the thrash resolver per simulated interval.

Read from the ``resolve_victims`` span around
``repro.sim.jax_engine._resolve_step_victims``; 0 where the window's
sizes never interfere (the resolver is bypassed)."""


def read(ctx):
    n = ctx.window["work"].get("intervals", 0)
    return 1e3 * ctx.spans.seconds["resolve_victims"] / n if n else None
