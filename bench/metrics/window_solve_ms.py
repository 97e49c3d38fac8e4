"""Mean time of one ``make_dmd_aggregate`` call (every co-fired pane in
one batched solve), in ms: the benchmark's span, host clock."""
from bench.stats import mean_span_ms


def read(run):
    return mean_span_ms(run.spans, "window_solve", run.window)
