"""The readers of the program's own instrumentation: the per-layer
metrics of its ``aires.*`` host spans, and the reads of its counters and
of the name scopes of its device operations (``bench.spantrace``).  On
views built by hand, on a trace with none of it (which they must read as
nothing), and on a small repair-grid study traced on the chip.
"""

import gzip
import json
import re
from pathlib import Path

import pytest

from bench import harness, spantrace, tracereduce
from repro.core import tracing

MAN = harness.manifest()
DATA = Path(__file__).resolve().parents[1] / "testdata"
#: the per-layer metrics that read the program's host spans
SPAN_METRICS = ("prepare_s", "transfer_s", "aggregate_s",
                "idle_unattributed_share")
#: those and the reads of the program's scopes and counters
SPAN_READERS = SPAN_METRICS + tuple(spantrace.READS)
SCOPE_READERS = {"draw_share": "aires.draw",
                 "crn_tile_share": "aires.crn_tile",
                 "hist_update_share": "aires.hist",
                 "ring_update_share": "aires.ring",
                 "repair_lane_share": "aires.repair_lane"}


def read(metric, view):
    """A per-layer metric reads the trace view; a read of the program's
    scopes or counters reads the span view around it."""
    if metric in spantrace.READS:
        return spantrace.READS[metric](view)
    if isinstance(view, spantrace.SpanView):
        view = view.view
    return harness.load_module("metrics", metric).read(view)


def test_every_reader_has_a_manifest_entry():
    entries = {m["name"]: m for m in MAN["per_layer"]}
    for name in SPAN_METRICS:
        assert entries[name]["source"] == "device_trace"
        assert entries[name]["moves"] == "trajectories_per_s"
        assert entries[name]["workloads"]


def test_readers_name_only_the_programs_spans_and_scopes():
    metrics = Path(harness.BENCH) / "metrics"
    texts = [(metrics / f"{name}.py").read_text() for name in SPAN_METRICS]
    texts.append(Path(spantrace.__file__).read_text())
    for text in texts:
        for used in re.findall(r'"(aires\.[a-z_]+)"', text):
            assert used in tracing.NAMES, used
    assert set(SCOPE_READERS.values()) <= set(tracing.NAMES)


def spans_view():
    """A study span of 1 s: the program on the chip from 0.3 s to 0.7 s,
    the host spans around it."""
    chip = tracereduce.Chip(ops=[("fusion", 3e8, 7e8)],
                            modules=[("sweep", 3e8, 7e8)])
    spans = [("aires.study", 0.05e9, 0.95e9, {"study": 0}),
             ("aires.prepare", 0.05e9, 0.3e9, {"rows": 64}),
             ("aires.wait", 0.3e9, 0.7e9, {}),
             ("aires.transfer", 0.7e9, 0.8e9,
              {"steps_run": 128, "chunks_run": 2, "active_row_chunks": 60,
               "real_rows": 36, "chunk": 64}),
             ("aires.aggregate", 0.8e9, 0.85e9, {"point": 0}),
             ("aires.aggregate", 0.85e9, 0.9e9, {"point": 1})]
    view = tracereduce.TraceView((0.0, 1e9), [chip],
                                 [(n, s, e) for n, s, e, _ in spans])
    return spantrace.SpanView(view, spans, [[]])


def add_span(sv, span):
    sv.spans.append(span)
    sv.view.host.append(span[:3])


@pytest.mark.parametrize("name", list(spantrace.READS))
def test_every_read_is_none_on_an_empty_view(name):
    view = tracereduce.TraceView((0.0, 1e9), [tracereduce.Chip()], [])
    assert spantrace.READS[name](spantrace.SpanView(view, [], [[]])) is None


def test_span_seconds():
    view = spans_view()
    assert read("prepare_s", view) == pytest.approx(0.25)
    assert read("transfer_s", view) == pytest.approx(0.1)
    assert read("aggregate_s", view) == pytest.approx(0.1)


def test_idle_unattributed_share():
    # idle: [0, 0.3) and [0.7, 1.0), 0.6 s; under no span: [0, 0.05) and
    # [0.9, 1.0) (the study root does not count)
    assert read("idle_unattributed_share", spans_view()) == \
        pytest.approx(100.0 * 0.15 / 0.6)


def test_idle_unattributed_share_counts_an_overlap_once():
    view = spans_view()
    add_span(view, ("aires.aggregate", 0.82e9, 0.95e9, {"point": 2}))
    assert read("idle_unattributed_share", view) == \
        pytest.approx(100.0 * 0.1 / 0.6)


def test_counters_on_the_transfer_span():
    view = spans_view()
    assert read("steps_run", view) == 128
    assert read("active_row_share", view) == pytest.approx(
        100.0 * 60 / (2 * 36))
    add_span(view, ("aires.transfer", 0.9e9, 0.95e9,
                    {"steps_run": 192, "chunks_run": 3,
                     "active_row_chunks": 12, "real_rows": 4}))
    assert read("steps_run", view) == 192
    assert read("active_row_share", view) == pytest.approx(
        100.0 * 72 / (2 * 36 + 3 * 4))


def test_a_loop_that_ran_no_full_chunk_has_no_active_row_share():
    view = spans_view()
    view.spans[3][3]["chunks_run"] = 0
    assert read("active_row_share", view) is None


def scoped_chip(t0, scale):
    """One chip whose sweep program runs 0.2 s from ``t0`` (ns), and its
    operations under each scope; ``scale`` stretches them."""
    ops = [("jit(_run_chunked)/aires.chunk/aires.draw/threefry2x32", 0.01),
           ("jit(_run_chunked)/aires.chunk/while/body/aires.crn_tile/tile",
            0.02),
           ("jit(_run_chunked)/aires.chunk/while/body/closed_call/"
            "aires.hist/add", 0.1),
           ("jit(_run_chunked)/aires.chunk/while/body/closed_call/"
            "aires.ring/select_n", 0.03),
           ("jit(_run_chunked)/aires.chunk/while/body/closed_call/"
            "aires.repair_lane/sub", 0.02),
           ("jit(_run_chunked)/aires.chunk/while/cond/reduce_or", 0.01),
           # a scope name inside another word is no scope
           ("jit(_run_chunked)/aires.histx/add", 0.005)]
    scopes, t = [], t0
    for path, d in ops:
        scopes.append((path, t, t + d * scale * 1e9))
        t += d * scale * 1e9
    return tracereduce.Chip(
        ops=[(f"op{i}", s, e) for i, (_, s, e) in enumerate(scopes)],
        modules=[("jit__run_chunked", t0, t0 + 0.2e9)]), scopes


def scoped_view(*chips):
    view = tracereduce.TraceView((0.0, 1e9), [c for c, _ in chips], [])
    return spantrace.SpanView(view, [], [s for _, s in chips])


def test_scope_shares_are_means_over_chips():
    view = scoped_view(scoped_chip(1e8, 1.0), scoped_chip(1e8, 0.5))
    want = {"draw_share": 0.01, "crn_tile_share": 0.02,
            "hist_update_share": 0.1, "ring_update_share": 0.03,
            "repair_lane_share": 0.02}
    for metric, seconds in want.items():
        # the same program interval on both chips: the mean of the
        # shares at scale 1 and 0.5
        assert read(metric, view) == pytest.approx(
            100.0 * 0.75 * seconds / 0.2), metric


def test_a_scope_no_op_ran_under_reads_nothing():
    chip, scopes = scoped_chip(1e8, 1.0)
    view = scoped_view(
        (chip, [sc for sc in scopes if "repair_lane" not in sc[0]]))
    assert read("repair_lane_share", view) is None
    assert read("hist_update_share", view) is not None


@pytest.fixture(scope="module")
def untraced():
    """A study recorded before the program had spans or scopes."""
    return spantrace.load(DATA / "whatif_tiny.xplane.pb.gz",
                          harness.STUDY_SPAN, 1)


def test_a_trace_without_the_programs_instrumentation_reads_nothing(
        untraced):
    assert untraced.spans == []
    assert untraced.scopes[0]
    for metric in SPAN_READERS:
        assert read(metric, untraced) is None, metric


def test_scopes_ride_beside_the_ops_unchanged(untraced):
    assert [(s, e) for _, s, e in untraced.view.chips[0].ops] == \
        [(s, e) for _, s, e in untraced.scopes[0]]
    paths = {p for p, _, _ in untraced.scopes[0]}
    assert any(p.startswith("jit(_run_chunked)/") for p in paths)


def xplane_pb2():
    return pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2",
        reason="the reference protobuf classes come with tensorflow")


@pytest.mark.parametrize("trace", ["whatif_tiny", "repair_tiny"])
def test_decoder_agrees_with_the_protobuf_classes(trace):
    pb = xplane_pb2()
    raw = gzip.decompress((DATA / f"{trace}.xplane.pb.gz").read_bytes())
    space = pb.XSpace()
    space.ParseFromString(raw)
    want = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:"):
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        paths = {}
        for md in plane.event_metadata.values():
            for st in md.stats:
                if names.get(st.metadata_id) != spantrace.SCOPE_STAT:
                    continue
                paths[md.name] = (st.str_value
                                  if st.WhichOneof("value") == "str_value"
                                  else names[st.ref_value])
        want[plane.name] = paths
    got = spantrace.op_scopes(raw)
    assert got == want
    assert got["/device:TPU:0"]


@pytest.fixture(scope="module")
def recorded():
    """A repair-grid study traced on a TPU v5e
    (``bench/testdata/repair_tiny.*``) and what every reader read from it
    when it was recorded."""
    rec = json.loads((DATA / "repair_tiny.json").read_text())
    view = spantrace.load(DATA / "repair_tiny.xplane.pb.gz",
                          harness.STUDY_SPAN, 1)
    return rec, view


def test_recorded_trace_is_small(recorded):
    assert (DATA / "repair_tiny.xplane.pb.gz").stat().st_size < 1_000_000


def test_recorded_program_time_lies_under_the_programs_scopes(recorded):
    _, view = recorded
    _, lo, hi = view.view.chips[0].program()
    inside = [(p, s, e) for p, s, e in view.scopes[0]
              if lo <= s and e <= hi]
    total = sum(e - s for _, s, e in inside)
    scoped = sum(e - s for p, s, e in inside
                 if any(c.startswith("aires.") for c in p.split("/")))
    assert total > 0 and scoped >= 0.9 * total


def test_recorded_trace_holds_one_set_of_host_spans(recorded):
    _, view = recorded
    counts = {n: len(view.named(n)) for n in tracing.NAMES[:5]}
    assert counts[tracing.STUDY] == counts[tracing.PREPARE] == 1
    assert counts[tracing.WAIT] == counts[tracing.TRANSFER] == 1
    assert counts[tracing.AGGREGATE] == 12      # the grid's points


@pytest.mark.parametrize("metric", SPAN_READERS)
def test_recorded_trace_reads_every_span_reader(recorded, metric):
    rec, view = recorded
    assert metric in rec["read"]
    assert read(metric, view) == pytest.approx(rec["read"][metric],
                                               rel=1e-9)


@pytest.mark.parametrize("metric", ["device_idle_share", "host_lead_s",
                                    "program_s", "host_tail_s",
                                    "race_kernel_share"])
def test_recorded_trace_reads_the_older_readers_as_recorded(recorded,
                                                             metric):
    rec, view = recorded
    assert read(metric, view) == pytest.approx(rec["read"][metric],
                                               rel=1e-9)
