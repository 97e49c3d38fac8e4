"""Property tests for WorkflowConfig (hypothesis; tests/test_workflow.py
carries a deterministic grid version)."""
from hypothesis import given, settings, strategies as st

from repro.workflow import WorkflowConfig


@given(n_producers=st.integers(1, 64),
       groups=st.one_of(st.none(), st.integers(1, 8)),
       executors=st.integers(1, 8),
       compress=st.sampled_from(["none", "zstd", "int8", "int8+zstd"]),
       backpressure=st.sampled_from(["block", "drop_oldest", "sample"]),
       transport=st.sampled_from(["inprocess", "loopback"]),
       trigger=st.floats(0.01, 30.0, allow_nan=False),
       min_batch=st.integers(1, 64),
       max_batch=st.integers(1, 128),
       delta=st.booleans(),
       inbound_bw=st.one_of(st.none(), st.floats(1e3, 1e9)))
@settings(max_examples=80, deadline=None)
def test_config_roundtrip_property(n_producers, groups, executors, compress,
                                   backpressure, transport, trigger,
                                   min_batch, max_batch, delta, inbound_bw):
    if groups is not None and groups > n_producers:
        groups = n_producers
    cfg = WorkflowConfig(n_producers=n_producers, n_groups=groups,
                         executors_per_group=executors, compress=compress,
                         backpressure=backpressure, transport=transport,
                         trigger_interval=trigger, min_batch=min_batch,
                         max_batch_records=max_batch, delta_encode=delta,
                         inbound_bw=inbound_bw).validate()
    assert WorkflowConfig.from_dict(cfg.to_dict()) == cfg


@given(st.integers(1, 512))
@settings(max_examples=40, deadline=None)
def test_auto_plan_is_always_valid(n):
    plan = WorkflowConfig(n_producers=n).validate().group_plan()
    assert 1 <= plan.n_groups <= n
    assert plan.n_executors >= plan.n_groups
