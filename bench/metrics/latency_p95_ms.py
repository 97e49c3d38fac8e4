"""95th percentile, in ms, over every analysis result emitted in the window,
of its emission time minus the creation stamp of the newest snapshot in
it.  Read as ``latency_p95_ms.sat`` in a saturated cell, where it is the
tail of a queue that runs at capacity, recorded and not judged."""
from bench.stats import tail_ms


def read(run):
    return tail_ms(run.results, run.window, 95.0)
