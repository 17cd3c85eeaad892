"""Host milliseconds per unit in the metric's host ``spans`` inside the
traced window: the length of their union, so that nested or repeated
spans count once. Where the metric names ``inside``, only spans that lie
within one of those spans count (the trace names a span by its own name,
not its path). None where no such span ran."""
from bench.lib.trace import union_length


def read(run, metric):
    if run.trace is None:
        return None
    lo, hi = run.trace.window
    names = set(metric["spans"])
    spans = [(a, b) for n, a, b in run.trace.host_spans if n in names]
    outer = set(metric.get("inside", ()))
    if outer:
        around = [(a, b) for n, a, b in run.trace.host_spans if n in outer]
        spans = [(a, b) for a, b in spans
                 if any(oa <= a and b <= ob for oa, ob in around)]
    clipped = [(max(a, lo), min(b, hi)) for a, b in spans if b > lo and a < hi]
    if not clipped:
        return None
    return 1e3 * union_length(clipped) / run.units
