"""Host milliseconds per database record outside the sweeps and the
micro-benchmark generation.

The self time of the program's ``perfdb.build``, ``experiment`` and
``scenario`` spans plus its ``perfdb.index`` span: the planner, the run
set's assembly, the curve's read-out and the index build; that is the
build less its ``sweep`` and ``scenario.trace`` spans
(``repro.runtime.tracing``). Per record completed in the window. Nothing
where the program has no such spans."""

SELF = ("perfdb.build", "experiment", "scenario")


def read(ctx):
    try:
        from repro.runtime import tracing
    except ImportError:  # a program without its own spans
        return None
    spans = tracing.snapshot()["spans"]
    n = ctx.window["work"].get("records", 0)
    if not n or "perfdb.build" not in spans:
        return None
    own = sum(spans[k]["self_seconds"] for k in SELF if k in spans)
    own += spans.get("perfdb.index", {}).get("seconds", 0.0)
    return 1e3 * own / n
