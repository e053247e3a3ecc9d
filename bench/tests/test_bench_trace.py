"""The trace-to-metrics reduction and the per-layer readers: on views
built by hand (interval arithmetic, the split of the study span into
lead, program and tail, the shares, a view with nothing to read), and on
a small trace recorded on the chip.
"""

import json
from pathlib import Path

import pytest

from bench import harness, tracereduce

MAN = harness.manifest()


def test_empty_view_reads_nothing():
    view = tracereduce.TraceView((0.0, 1e9), [tracereduce.Chip()], [])
    for m in MAN["per_layer"]:
        assert harness.load_module("metrics", m["name"]).read(view) is None


def test_intervals_union_and_gaps():
    spans = [(0.0, 2.0), (1.0, 3.0), (5.0, 6.0)]
    assert tracereduce.union_ns(spans) == 4.0
    assert tracereduce.gaps(spans, 0.0, 8.0) == [(3.0, 5.0), (6.0, 8.0)]


def test_synthetic_span_splits_into_lead_program_and_tail():
    chip = tracereduce.Chip(ops=[("fusion", 2e8, 5e8), ("event_race", 5e8,
                                                        6e8)],
                            modules=[("small", 1e8, 1.5e8),
                                     ("sweep", 2e8, 7e8)])
    view = tracereduce.TraceView((0.0, 1e9), [chip], [("bench.study", 0.0,
                                                       1e9)])
    read = {m: harness.load_module("metrics", m).read(view)
            for m in ("host_lead_s", "program_s", "host_tail_s",
                      "device_idle_share", "race_kernel_share")}
    assert read["host_lead_s"] == pytest.approx(0.2)
    assert read["program_s"] == pytest.approx(0.5)
    assert read["host_tail_s"] == pytest.approx(0.3)
    assert read["device_idle_share"] == pytest.approx(60.0)
    assert read["race_kernel_share"] == pytest.approx(20.0)


def test_breakdown_on_two_chips():
    host = [("bench.study", 0.0, 1e9), ("aggregate", 7e8, 1e9)]
    chips = [tracereduce.Chip(ops=[("fusion.1", 2e8, 7e8)],
                              modules=[("sweep", 2e8, 7e8)]),
             tracereduce.Chip(ops=[("fusion.1", 2e8, 6e8)],
                              modules=[("sweep", 2e8, 6e8)])]
    view = tracereduce.TraceView((0.0, 1e9), chips, host)
    b = view.breakdown()
    assert b["device_ops"] == [["fusion.1", pytest.approx(0.45)]]
    assert b["idle_gaps"][0] == ["aggregate", pytest.approx(0.3)]
    assert view.busy_s() == pytest.approx(0.45)


def test_loop_events_around_ops_are_not_busy():
    ops = [("while", 1.0, 9.0), ("fusion", 2.0, 3.0), ("event_race", 5.0,
                                                        6.0),
           ("copy", 10.0, 11.0)]
    kept = tracereduce.leaves(ops)
    assert sorted(n for n, _, _ in kept) == ["copy", "event_race", "fusion"]
    chip = tracereduce.Chip(ops=kept)
    assert chip.busy_ns() == 3.0


@pytest.fixture(scope="module")
def recorded():
    """A study traced on a TPU v5e (``bench/testdata/whatif_tiny.*``):
    what its trace holds, read apart from this reduction, and what the
    readers read from it when it was recorded."""
    data = Path(__file__).resolve().parents[1] / "testdata"
    rec = json.loads((data / "whatif_tiny.json").read_text())
    view = tracereduce.load(data / "whatif_tiny.xplane.pb.gz",
                            harness.STUDY_SPAN, 1)
    return rec, view


def test_recorded_trace_holds_one_race_call_per_step(recorded):
    rec, view = recorded
    calls = [n for n, _, _ in view.chips[0].ops
             if tracereduce.RACE_KERNEL in n]
    assert len(calls) == rec["steps"]
    assert view.chips[0].program()[0].startswith(rec["program"])


def test_recorded_span_splits_into_lead_program_and_tail(recorded):
    rec, view = recorded
    assert view.span_s == pytest.approx(rec["span_s"], abs=1e-9)
    parts = [harness.load_module("metrics", m).read(view)
             for m in ("host_lead_s", "program_s", "host_tail_s")]
    assert sum(parts) == pytest.approx(view.span_s, rel=1e-9)
    # the leaf ops lie inside the program, and their union under that
    # of every op, the loops that enclose them included
    assert 0.0 < view.busy_s() <= parts[1]
    assert view.busy_s() <= rec["all_ops_union_s"] + 5e-5


@pytest.mark.parametrize("metric", sorted(json.loads(
    (Path(__file__).resolve().parents[1] / "testdata" / "whatif_tiny.json")
    .read_text())["read"]))
def test_recorded_trace_reads_as_recorded(recorded, metric):
    rec, view = recorded
    got = harness.load_module("metrics", metric).read(view)
    assert got == pytest.approx(rec["read"][metric], rel=1e-9)
