"""Logical-axis rule resolution: divisibility fallback, no mesh-axis reuse."""
import numpy as np
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P


class FakeMesh:
    """spec_for only consults mesh.shape."""
    def __init__(self, shape):
        self.shape = shape


from repro.launch.shardings import spec_for, DEFAULT_RULES

POD = FakeMesh({"data": 16, "model": 16})
MULTI = FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_basic_tp_fsdp():
    s = spec_for((4096, 128, 128), ("embed", "heads", "head_dim"), POD)
    assert s == P("data", "model", None)


def test_divisibility_fallback_replicates():
    # 56 heads: 16 does not divide -> replicate
    s = spec_for((4096, 56, 128), ("embed", "heads", "head_dim"), POD)
    assert s == P("data", None, None)


def test_batch_stacks_pod_and_data():
    s = spec_for((256, 4096), ("batch", "seq"), MULTI)
    assert s == P(("pod", "data"), None)
    # batch=1: nothing divides -> replicated; cache_seq picks up data
    s = spec_for((1, 524288, 8, 128),
                 ("batch", "cache_seq", "kv_heads", "head_dim"), MULTI)
    assert s == P(None, "data", None, None)


def test_no_axis_reuse_within_tensor():
    # experts takes model; ffn_e must NOT also get model
    s = spec_for((128, 7168, 4864), ("experts", "embed", "ffn_e"), POD)
    assert s == P("model", "data", None)


@given(
    dims=st.lists(st.integers(1, 4096), min_size=1, max_size=5),
    axes=st.lists(st.sampled_from(list(DEFAULT_RULES.keys()) + [None]),
                  min_size=1, max_size=5),
)
@settings(max_examples=200, deadline=None)
def test_spec_invariants(dims, axes):
    n = min(len(dims), len(axes))
    dims, axes = tuple(dims[:n]), tuple(axes[:n])
    spec = spec_for(dims, axes, MULTI)
    used = []
    for dim, part in zip(dims, spec):
        if part is None:
            continue
        parts = part if isinstance(part, tuple) else (part,)
        total = 1
        for ax in parts:
            used.append(ax)
            total *= MULTI.shape[ax]
        assert dim % total == 0          # always evenly divisible
    assert len(used) == len(set(used))   # no mesh axis used twice
