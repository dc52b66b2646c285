"""The whole engine step's share of the chip's roofline: the least time of
the step's useful work (the served path's ``step_work``) over the device
time of the step programs in the window, in percent."""
import work

STEP_PROGRAM = "jit_step"


def read(run):
    if run.trace is None:
        return None
    t = run.trace.module_seconds(STEP_PROGRAM)
    if t <= 0:
        return None
    least, _ = work.least_seconds(
        work.total(run.step_work, run.cfg, run.counts), run.peak)
    return 100.0 * least / t
