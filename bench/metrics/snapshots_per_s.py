"""Snapshots analysed per second: every snapshot the stream engine took
through the analysis plan (window insert, and every pane solve the insert
fired) in a micro-batch that completed inside the window, over the
window's length.  Each such snapshot is in a pane result by the end of the
run, or the run is not correct."""
from bench.stats import in_window, rate


def read(run):
    n = sum(k for t, k in run.facts.get("processed", ())
            if in_window(t, run.window))
    return rate(n, run.window) if n else None
