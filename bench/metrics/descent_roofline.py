"""Share of its roofline that the tree-descent kernel reaches: the least
time the descent work of the consumed trials needs at the chip's peaks
(``work.descent``) over the device time of ``ndpp_tree_descent`` in the
trace."""
import work


def read(run):
    if run.trace is None or run.peak is None:
        return None
    t = run.trace.kernel_seconds("ndpp_tree_descent")
    if t <= 0:
        return None
    f = run.facts
    flops, nbytes = work.descent(run.consumed_trials("rejection"),
                                 f["e_size"], f["depth"], f["r"])
    least, _ = work.least_seconds(flops, nbytes, run.peak)
    return 100.0 * least / t
