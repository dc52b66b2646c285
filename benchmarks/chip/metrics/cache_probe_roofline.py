"""``cache_probe`` kernel's share of its roofline: least time of its useful
work (``work.cache_probe``) over the device time of its operations in the
window, in percent."""
import work

KERNEL = "cache_probe"


def read(run):
    if run.trace is None:
        return None
    t = run.trace.op_seconds(KERNEL)
    if t <= 0:
        return None
    least, _ = work.least_seconds(
        work.total(work.cache_probe, run.cfg, run.counts), run.peak)
    return 100.0 * least / t
