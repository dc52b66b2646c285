"""The dense half's share of the chip's peak: its useful operations
(``work_rank.dense_half``: both MLPs and the interaction's upper triangle,
at the configuration's widths) at the peak rate over the device time of
the ``engine.dense`` scope in the window, in percent."""
import work
import work_rank
from repro.obs import tracing as names


def read(run):
    t = run.trace.scope_s(names.SCOPE_DENSE) if run.trace else None
    if not t:
        return None
    least, _ = work.least_seconds(
        work.total(work_rank.dense_half, run.cfg, run.counts), run.peak)
    return 100.0 * least / t
