"""Host milliseconds of the demotion ranking per simulated interval.

Read from the ``demote_rank_host`` spans around
``GlobalDemoteRank(...)`` and ``_tie_groups`` in
``repro.sim.jax_engine``."""


def read(ctx):
    n = ctx.window["work"].get("intervals", 0)
    return 1e3 * ctx.spans.seconds["demote_rank_host"] / n if n else None
