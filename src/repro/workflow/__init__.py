"""repro.workflow — the declarative HPC→Cloud workflow API.

Public surface:

* :class:`WorkflowConfig` — one validated config (topology + endpoint +
  broker + engine knobs) with a lossless ``to_dict``/``from_dict``.
* :class:`Session` — context manager owning endpoint creation, broker
  construction, engine/plan lifecycle, and ordered teardown.
* :class:`FieldHandle` — typed producer handle (``write``/``write_batch``).
* :class:`ElasticityConfig` — the control-plane knob block; with
  ``enabled=True`` the Session owns a telemetry bus + ElasticController
  that holds the p99 QoS target by scaling executors, adapting wire batch
  caps, and recovering from endpoint/executor failure.

The analysis surface is the **stream-operator API**
(:mod:`repro.streaming.operators`, re-exported here): an
:class:`OperatorPipeline` of typed operators (``Map``/``Filter``/``KeyBy``/
``TumblingWindow``/``SlidingWindow``/``Aggregate``/``Sink``), each with an
ordering contract (``ordered`` | ``unordered`` | ``keyed``) and a
parallelism hint, compiled to an :class:`ExecutionPlan` the engine honors —
order-insensitive stages run intra-stream parallel, windows hold keyed
state with snapshot/restore.  It is the one way to attach analysis: a
per-micro-batch callback is a one-stage plan,
``OperatorPipeline(granularity="batch").map("analyze", fn,
ordering="ordered")``.

The paper's Listing 1.1 C API (``broker_connect``/``broker_init``/
``broker_write``/``broker_finalize`` in :mod:`repro.core.api`) is kept as a
thin, deprecated compatibility shim over :class:`Session`.
"""
from repro.runtime.controller import ElasticityConfig
from repro.streaming.operators import (Aggregate, ExecutionPlan, Filter,
                                       KeyBy, Map, OperatorPipeline, Sink,
                                       SlidingWindow, TumblingWindow,
                                       WindowPane)
from repro.workflow.config import WorkflowConfig
from repro.workflow.session import FieldHandle, Session

__all__ = ["WorkflowConfig", "Session", "FieldHandle",
           "ElasticityConfig", "OperatorPipeline", "ExecutionPlan",
           "Map", "Filter", "KeyBy", "TumblingWindow", "SlidingWindow",
           "Aggregate", "Sink", "WindowPane"]
