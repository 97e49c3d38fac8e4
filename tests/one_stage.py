"""Test helper: a per-micro-batch callback ``fn(key, records)`` as the
one-stage operator plan the engine runs.  Each ``Result.value`` is fn's
return value (None when it returns None, the exception when it raises),
in sticky, ticketed per-stream order."""
from repro.streaming.operators import OperatorPipeline


def one_stage(fn) -> OperatorPipeline:
    """Pass to ``Session(pipeline=...)``; ``.compile()`` it for a bare
    ``StreamEngine``."""
    return OperatorPipeline(granularity="batch").map("analyze", fn,
                                                     ordering="ordered")
