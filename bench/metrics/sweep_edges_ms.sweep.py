"""Host milliseconds per sweep outside its intervals.

The program's ``sweep.eligibility`` (the unique-ids pass over the whole
trace), ``sweep.setup`` (slice pools, the tier export) and
``sweep.import`` (the final tier pull and import) spans, over the calls
of its ``sweep`` span (``repro.runtime.tracing``). Nothing where the
program has no such spans."""

SPANS = ("sweep.eligibility", "sweep.setup", "sweep.import")


def read(ctx):
    try:
        from repro.runtime import tracing
    except ImportError:  # a program without its own spans
        return None
    spans = tracing.snapshot()["spans"]
    sweeps = spans.get("sweep", {}).get("calls")
    found = [spans[k]["seconds"] for k in SPANS if k in spans]
    return 1e3 * sum(found) / sweeps if sweeps and found else None
