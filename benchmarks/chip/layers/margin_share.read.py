"""Share of point reads that streamed the table: the count of the program
span `read.margin` (the margin step, run when some view's waters miss)
over that of `read.probe` (every read), both from the program's
`profiled.span.<name>.seconds` histograms: the spans opened while the
window's profile was collected. None where the program has no such
spans."""


def read(run):
    hist = run.ex.metrics.snapshot()["histograms"]
    probes = hist.get("profiled.span.read.probe.seconds", {}).get("count", 0)
    if not probes:
        return None
    margins = hist.get("profiled.span.read.margin.seconds", {}).get("count", 0)
    return 100.0 * margins / probes
