"""Snapshots folded per ``StreamingDMD.update_batch`` over the window: the
cfd deployment's stage counts each update and the snapshots in it.  At one
output step in flight a region never has a second step waiting, so this
reads exactly 1.0: a guard on the traffic's shape."""
from bench.stats import delta


def read(run):
    updates = delta(run.begin, run.end, "updates")
    return delta(run.begin, run.end, "rows") / updates if updates else None
