"""How evenly the band kernel's rows fall on the shards of a mesh: over the
window's launches, the widest shard's streamed rows summed, times the
shards, over all shards' streamed rows summed. 1.0 means every shard
streamed as many rows as the others; a launch ends with its widest shard,
so this is how much longer the window's launches took than an even split
would, the split that `band_kernel_roofline` assumes. Each launch weighs
with its rows, as its time does. Read from the program's
`profiled.band.shard_window_rows_max` and `profiled.band.window_rows`
histograms (launches while the window's profile was collected) and the
view's `shards`; one shard reads 1.0. None where the program keeps no such
histograms or no launch fell in the window."""


def read(run):
    snap = run.ex.metrics.snapshot()
    hist = snap["histograms"]
    widest = hist.get("profiled.band.shard_window_rows_max")
    total = hist.get("profiled.band.window_rows")
    shards = snap.get(f"view.{run.vname}", {}).get("shards")
    if not widest or not total or not widest["count"] or not total["sum"] \
            or shards is None:
        return None
    return shards * widest["sum"] / total["sum"]
