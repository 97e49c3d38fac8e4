"""Mean time of one ``StreamingDMD.eigenvalues()`` call (past the snapshot
window, the d×d Gram eigensolve and the host's small eigensolve), in ms:
the benchmark's span, host clock, which holds the wait for the device and
for the other regions' solves queued on it."""
from bench.stats import mean_span_ms


def read(run):
    return mean_span_ms(run.spans, "stream_solve", run.window)
