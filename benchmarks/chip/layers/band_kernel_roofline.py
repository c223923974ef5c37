"""The band kernel's share of its roofline: the mean time a launch's need
takes at the chip's peak HBM bandwidth, over the mean device time of a
launch. The need is what an exact relabel must read: the rows of the union
over views of the Lemma 3.1 band (d f32 each, with k labels read and
written), however many rows the kernel streams."""


def read(run):
    t, need = run.trace, run.launch_need_s
    if not t or not t["kernel_launches"] or not need:
        return None
    return 100.0 * (sum(need) / len(need)) / (
        t["kernel_s"] / t["kernel_launches"])
