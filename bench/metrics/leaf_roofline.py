"""Share of its roofline that the leaf-scoring kernel reaches: the least
time the leaf work of the consumed trials needs at the chip's peaks
(``work.leaf``) over the device time of ``ndpp_bilinear_batched`` in the
trace."""
import work


def read(run):
    if run.trace is None or run.peak is None:
        return None
    t = run.trace.kernel_seconds("ndpp_bilinear_batched")
    if t <= 0:
        return None
    f = run.facts
    flops, nbytes = work.leaf(run.consumed_trials("rejection"),
                              f["e_size"], f["block"], f["r"])
    least, _ = work.least_seconds(flops, nbytes, run.peak)
    return 100.0 * least / t
