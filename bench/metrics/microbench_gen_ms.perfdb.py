"""Host milliseconds generating the micro-benchmark trace, per record.

Read from the ``microbench_gen`` span around
``repro.core.tuner._microbench_trace``."""


def read(ctx):
    n = ctx.window["work"].get("records", 0)
    return 1e3 * ctx.spans.seconds["microbench_gen"] / n if n else None
