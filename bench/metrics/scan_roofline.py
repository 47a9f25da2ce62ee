"""Scan roofline share: the least time of the scan work each ``search``
call's queries need (``harness/work.py``), summed over the calls, over the
device time inside those calls, in %. Work the implementation adds (padding,
re-reads, escalation, full scans for a selective predicate) shows as lost
share."""
from harness import work


def read(ctx):
    r = ctx.reduction
    if r is None or ctx.peak is None or r.search_device_s <= 0:
        return None
    least = sum(work.least_time(*ctx.scan_work(q), ctx.peak)
                for q, _ in r.calls)
    return 100.0 * least / r.search_device_s
