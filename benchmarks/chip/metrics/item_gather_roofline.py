"""The item side's share of its roofline: least time of its useful work
(``work_rank.item_gather``: valid item lookups at their stored size and
the pooled item bags) over the device time of the ``engine.item`` scope in
the window, in percent."""
import work
import work_rank
from repro.obs import tracing as names


def read(run):
    t = run.trace.scope_s(names.SCOPE_ITEM) if run.trace else None
    if not t:
        return None
    least, _ = work.least_seconds(
        work.total(work_rank.item_gather, run.cfg, run.counts), run.peak)
    return 100.0 * least / t
