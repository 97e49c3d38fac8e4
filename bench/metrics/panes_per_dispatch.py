"""Window panes per ``BatchAggregate`` call over the window, from the
engine's ``batch_agg`` counters: panes solved together in one dispatch."""
from bench.stats import delta


def read(run):
    calls = delta(run.begin, run.end, "agg_batches")
    return delta(run.begin, run.end, "agg_items") / calls if calls else None
