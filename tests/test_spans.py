"""The program's own spans (``repro.runtime.telemetry.span``).

With no profiler running a span records nothing and changes no result.
Under a ``jax.profiler`` trace, a small windowed-DMD session (a few ranks,
int8+zstd frames, a keyed tumbling window into
``BatchAggregate(make_dmd_aggregate(...))``) records every layer's span on
the host plane, one line per thread, with the counts each span carries as
its arguments.  Online DMD (``StreamingDMD``) records one span per update
and one per eigensolve, with the route the solve took."""
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.analysis.dmd import (_WARM_MAX_ITERS, _WARM_MIN_ITERS,
                                StreamingDMD, _pad_cols, _pad_rows,
                                make_dmd_aggregate)
from repro.runtime.telemetry import span
from repro.workflow import OperatorPipeline, Session, WorkflowConfig

RANKS, D, PANE, STEPS, RANK = 4, 48, 8, 48, 2

SPAN_NAMES = {
    "broker.enqueue", "broker.encode", "broker.send", "endpoint.decode",
    "engine.trigger", "engine.run", "operators.insert", "operators.fire",
    "operators.batch_aggregate", "analysis.prepare", "analysis.solve",
    "analysis.fill", "analysis.transfer", "analysis.eig"}


def _run_session() -> dict:
    """Write STEPS steps of RANKS seeded snapshots through a windowed-DMD
    session and close it; returns what the tests compare."""
    data = np.random.default_rng(5).standard_normal(
        (RANKS, STEPS, D)).astype(np.float32)
    dmd = make_dmd_aggregate(
        rank=RANK, n_features=D,
        prepare=lambda vals: [r.payload for r in
                              sorted(vals, key=lambda r: r.step)])

    def window_stage(items):     # each pane's eigenvalues by its first step
        return list(zip([min(r.step for r in v) for _k, v in items],
                        dmd(items)))

    pipeline = (OperatorPipeline(granularity="record")
                .key_by("rank", lambda key, rec: key)
                .tumbling_window("panes", size_s=float(PANE),
                                 allowed_lateness_s=float(STEPS))
                .batch_aggregate("dmd", window_stage)
                .sink("eigs"))
    session = Session(WorkflowConfig(
        n_producers=RANKS, n_groups=2, executors_per_group=2,
        compress="int8+zstd", backpressure="block", trigger_interval=0.02,
        transport="inprocess"), pipeline=pipeline)
    field = session.open_field("field", shape=(D,))
    for s in range(STEPS):
        field.write_batch(s, list(data[:, s]), ranks=list(range(RANKS)),
                          t=float(s))
    session.flush(timeout=60.0)
    stats = session.close()
    return {"sent": stats.sent, "dropped": stats.dropped,
            "steal_wakes": session.engine.metrics()["steal_wakes"],
            "items": session.exec_plan.batch_stats()["dmd"]["items"],
            "eigs": {(k, step): e
                     for k, (step, e), _t in session.results("eigs")}}


def _read_spans(log_dir: Path) -> list[dict]:
    """Every ``repro.*`` host event of the one trace under ``log_dir``:
    name (prefix dropped), thread line, start, end, arguments."""
    (path,) = Path(log_dir).glob("**/*.xplane.pb")
    out = []
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line_no, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append({"name": e.name[len("repro."):],
                                "line": line_no, "start": e.start_ns,
                                "end": e.start_ns + e.duration_ns,
                                "args": dict(e.stats)})
    return out


def _trace(log_dir: Path, fn):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        return fn()
    finally:
        jax.profiler.stop_trace()


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    log_dir = tmp_path_factory.mktemp("trace")
    out = _trace(log_dir, _run_session)
    return out, _read_spans(log_dir)


def test_untraced_spans_record_nothing_and_change_no_result(traced,
                                                             tmp_path):
    untraced = _run_session()
    with span("engine.run", records=3) as sp:
        sp.set_metadata(queued_us=1)
    # a trace started afterwards holds none of the spans run before it
    _trace(tmp_path, lambda: None)
    assert _read_spans(tmp_path) == []
    out, _spans = traced
    assert untraced["dropped"] == out["dropped"] == 0
    assert untraced["sent"] == out["sent"] == RANKS * STEPS
    assert untraced["eigs"].keys() == out["eigs"].keys()
    assert len(untraced["eigs"]) == RANKS * STEPS // PANE
    # which panes co-fire into one batched solve varies from run to run,
    # and the vmapped float32 solve rounds differently per batch size
    for key, eigs in untraced["eigs"].items():
        np.testing.assert_allclose(eigs, out["eigs"][key], rtol=1e-4,
                                   atol=1e-6)


def test_traced_session_records_every_span(traced):
    _out, spans = traced
    assert {sp["name"] for sp in spans} == SPAN_NAMES
    for sp in spans:
        assert sp["end"] >= sp["start"]


def test_span_counts_match_the_program_counters(traced):
    out, spans = traced

    def total(name, arg):
        return sum(sp["args"][arg] for sp in spans if sp["name"] == name)

    assert total("broker.encode", "records") == out["sent"]
    assert total("endpoint.decode", "records") == out["sent"]
    assert total("operators.insert", "records") == out["sent"]
    assert total("operators.batch_aggregate", "items") == out["items"]
    assert total("analysis.solve", "panes") == out["items"]
    assert total("analysis.solve", "snapshots") == out["sent"]
    assert total("operators.insert", "late") == 0
    # the plan spreads each stream's batches (no run to migrate) and no
    # executor is retired, so only dispatches wake executors to steal
    assert total("engine.trigger", "woken") == out["steal_wakes"]
    # one bucket per solve (every pane holds PANE snapshots): its slab is
    # (k_pad, m_pad, d) float32, d as the configuration gives it
    solves = [sp for sp in spans if sp["name"] == "analysis.solve"]
    transfers = [sp for sp in spans if sp["name"] == "analysis.transfer"]
    assert len(transfers) == len(solves)
    for solve in solves:
        (t,) = [t for t in transfers if t["line"] == solve["line"]
                and solve["start"] <= t["start"] <= t["end"] <= solve["end"]]
        assert t["args"]["bytes"] == (_pad_rows(solve["args"]["panes"])
                                      * _pad_cols(PANE) * D * 4)
    k_pad = sum(_pad_rows(sp["args"]["panes"]) for sp in solves)
    assert total("analysis.transfer", "bytes") == \
        k_pad * _pad_cols(PANE) * D * 4


def test_slab_fill_is_one_copy_per_bucket(traced):
    """Every decoded payload is a contiguous row of D floats, so each
    bucket's slab is gathered in one host copy call."""
    _out, spans = traced
    slabs = [sp for sp in spans
             if sp["name"] == "analysis.fill" and "slab_bytes" in sp["args"]]
    transfers = [sp for sp in spans if sp["name"] == "analysis.transfer"]
    assert len(slabs) == len(transfers) > 0
    for sp in slabs:
        assert sp["args"]["copies"] == 1
        assert sp["args"]["slab_bytes"] % (_pad_cols(PANE) * D * 4) == 0


def test_engine_run_nests_the_insert_on_its_thread(traced):
    _out, spans = traced
    runs = [sp for sp in spans if sp["name"] == "engine.run"]
    inserts = [sp for sp in spans if sp["name"] == "operators.insert"]
    assert len(inserts) == len(runs) > 0
    for ins in inserts:
        (run,) = [r for r in runs if r["line"] == ins["line"]
                  and r["start"] <= ins["start"] <= ins["end"] <= r["end"]]
        assert run["args"]["records"] == ins["args"]["records"]
        assert run["args"]["queued_us"] >= 0
        assert run["args"]["stream"].startswith("field/")


STREAM_WINDOW = 4


def _stream_updates() -> list[np.ndarray]:
    """Feed one StreamingDMD blocks of 1, 2, 3 and 1 seeded snapshots,
    asking for its eigenvalues twice after each; returns every answer."""
    data = np.random.default_rng(7).standard_normal((7, D)).astype(np.float32)
    sd = StreamingDMD(n_features=D, window=STREAM_WINDOW, rank=RANK)
    out = []
    for lo, hi in ((0, 1), (1, 3), (3, 6), (6, 7)):
        sd.update_batch(data[lo:hi])
        out += [sd.eigenvalues(), sd.eigenvalues()]
    return out


def test_stream_spans_carry_rows_padding_bytes_and_route(tmp_path):
    _trace(tmp_path, _stream_updates)
    spans = _read_spans(tmp_path)
    updates = [sp["args"] for sp in spans
               if sp["name"] == "analysis.stream_update"]
    # the first block pairs rows - 1 snapshots, later ones chain through
    # the previous block's last snapshot; X and Y ship padded, float32
    assert [(a["rows"], a["padded_rows"]) for a in updates] == \
        [(1, 0), (2, 2), (3, 4), (1, 1)]
    for a in updates:
        assert a["bytes"] == 2 * a["padded_rows"] * D * 4
    solves = [sp["args"] for sp in spans
              if sp["name"] == "analysis.stream_eig"]
    assert [(a["route"], a["n_seen"]) for a in solves] == \
        [("exact", 1), ("cached", 1), ("exact", 3), ("cached", 3),
         ("gram", 6), ("cached", 6), ("warm", 7), ("cached", 7)]
    # the first Gram solve is the full eigh; the next starts from its basis
    # and converges within the warm iterations
    gram = [(a["route"], a["iters"], a["fell_back"]) for a in solves
            if "iters" in a]
    assert [r for r, _, _ in gram] == ["gram", "warm"]
    assert gram[0][1:] == (0, False)
    assert _WARM_MIN_ITERS <= gram[1][1] <= _WARM_MAX_ITERS
    assert not gram[1][2]


def test_untraced_stream_spans_record_nothing_and_change_no_result(tmp_path):
    traced = _trace(tmp_path / "on", _stream_updates)
    untraced = _stream_updates()
    _trace(tmp_path / "after", lambda: None)
    assert _read_spans(tmp_path / "after") == []
    for a, b in zip(traced, untraced, strict=True):
        np.testing.assert_array_equal(a, b)
