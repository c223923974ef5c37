"""Host work of one maintenance round, not counting its waits on the
device: the summed seconds of the program spans `round.sgd`,
`round.waters`, `round.update` and `round.reorganize` (SGD, the Eq. 2
waters, and the dispatch of the band update or the reorganize) over the
rounds, each of which opens one `round.sgd`. Read from the program's
`profiled.span.<name>.seconds` histograms: the spans opened while the
window's profile was collected. None where the program has no such
spans."""

HOST = ("round.sgd", "round.waters", "round.update", "round.reorganize")


def read(run):
    hist = run.ex.metrics.snapshot()["histograms"]
    spans = {n: hist.get(f"profiled.span.{n}.seconds") for n in HOST}
    rounds = (spans["round.sgd"] or {}).get("count", 0)
    if not rounds:
        return None
    return 1e3 * sum(h["sum"] for h in spans.values() if h) / rounds
