"""The band kernel's share of its roofline: the mean time a round's need
takes at the peak HBM bandwidth of the cell's chips together, over one
chip's mean device time of a launch (the trace's kernel time over its
launches, both summed over the chips). The need is what an exact relabel
must read: the rows of the union over views of the Lemma 3.1 band (d f32
each, with k labels read and written), however many rows the kernel
streams. On a mesh each chip relabels its own rows, and the need is taken
as spread evenly over the `chips` that share the table; with one chip this
is the mean need over the mean launch time."""


def read(run):
    t, need = run.trace, run.launch_need_s
    if not t or not t["kernel_launches"] or not need:
        return None
    chips = int(run.cell["chips"])
    return 100.0 * (sum(need) / len(need) / chips) / (
        t["kernel_s"] / t["kernel_launches"])
