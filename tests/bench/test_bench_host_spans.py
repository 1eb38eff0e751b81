"""The reduction of the engine's host spans (``bench/host_spans.py``): on
hand-made intervals and spans, on the small chip trace recorded before the
engine had spans (``data/trace``), and on one recorded with them
(``data/trace_spans``, ``bench/record_trace.py`` on a TPU v5e chip)."""
import gzip
import json

import pytest

from bench import harness, host_spans, runner, trace
from bench.host_spans import HostEvent
from repro.obs.spans import Span

from test_bench_trace import PROGRAMS, TRACE, view  # noqa: F401  (fixture)

SPANS = TRACE.parent / "trace_spans"


def _window(directory):
    """The harness's window recorded beside a trace (``window.json``)."""
    raw = json.loads((directory / "window.json").read_text())
    for k in ("reqs", "carried"):
        raw[k] = [harness.WinReq(**r) for r in raw.get(k, [])]
    raw["steps"] = [harness.Step(**dict(s, decoded=[tuple(x) for x in s["decoded"]]
                                        if s["decoded"] else None)) for s in raw["steps"]]
    raw["queue_depth"] = [tuple(x) for x in raw["queue_depth"]]
    return harness.Window(**raw)


def _red(busy, host, kinds, lo=0, hi=100):
    return trace.Reduction(lo=lo, hi=hi, modules=[], ops=[], busy=busy,
                           host=[trace.Event(n, s, e) for n, s, e in host], step_kinds=kinds)


def _ev(name, s, e, **attrs):
    return HostEvent(name, s, e, attrs)


# a window of 100 ns: a decode step [10, 40) and an admission [50, 90)
HOST = [("bench.step", 10, 40), ("bench.step", 50, 90), ("bench.wait", 92, 99)]
EVENTS = [
    _ev("engine.step", 11, 39), _ev("engine.decode", 12, 38), _ev("engine.launch", 12, 14),
    _ev("engine.sync", 30, 37),
    _ev("engine.step", 51, 89), _ev("engine.admit", 51, 89, n=1, req_ids="7"),
    _ev("store.fetch", 52, 70, nbytes=1000), _ev("store.checksum", 55, 69, nbytes=1000),
    _ev("engine.assemble", 71, 80, bucket_bytes=4096, stored_bytes=1000),
    _ev("engine.h2d", 78, 80, nbytes=4096), _ev("engine.launch", 80, 81),
]
BUSY = [(14, 30), (81, 86)]


def test_covered():
    busy = [(10, 20), (30, 40)]
    assert host_spans.covered(busy, 0, 100) == 20
    assert host_spans.covered(busy, 15, 35) == 10
    assert host_spans.covered(busy, 20, 30) == 0
    assert host_spans.covered([], 0, 5) == 0


def test_decode_host_ms_is_the_uncovered_time_of_decode_steps():
    red = _red(BUSY, HOST, ["decode", "admit"])
    # one decode step: 28 ns, 16 of them busy -> 12 ns uncovered
    assert host_spans.decode_steps(EVENTS) == [EVENTS[0]]
    assert host_spans.decode_host_ms(EVENTS, red) == pytest.approx(12e-6)
    assert host_spans.decode_host_ms(EVENTS[4:], red) is None


def test_pieces_take_the_innermost_label():
    got = host_spans.pieces(
        [(0, 10, "a"), (2, 5, "b"), (3, 4, "c"), (8, 10, "d"), (11, 12, "e")], 0, 13)
    other = "host.other"
    assert got == [(0, 2, "a", "a"), (2, 3, "b", "a"), (3, 4, "c", "a"), (4, 5, "b", "a"),
                   (5, 8, "a", "a"), (8, 10, "d", "a"), (10, 11, other, other),
                   (11, 12, "e", "e"), (12, 13, other, other)]
    assert host_spans.pieces([], 0, 3) == [(0, 3, "host.other", "host.other")]


def test_idle_by_span_parts_sum_to_the_idle_time():
    red = _red(BUSY, HOST, ["decode", "admit"])
    got = host_spans.idle_by_span(EVENTS, red)
    idle = got["idle_s"]
    assert sum(idle.values()) == pytest.approx(got["window_idle_s"])
    assert got["window_idle_s"] == pytest.approx((red.hi - red.lo - 21) / 1e9)
    assert idle["store.checksum"] == pytest.approx(14e-9)
    assert idle["engine.h2d"] == pytest.approx(2e-9)
    assert idle["engine.assemble"] == pytest.approx(7e-9)
    assert idle["wait"] == pytest.approx(7e-9)
    assert idle["step.decode"] == pytest.approx(2e-9)  # the harness around engine.step
    # the admission step [50, 90) idles 35 ns: 5 in engine.admit's own time
    # (engine.step shares its extent), 2 in the harness's; 28 in named spans
    assert got["admit_idle_s"] == pytest.approx(35e-9)
    assert idle["engine.admit"] == pytest.approx(5e-9) and "engine.step" in idle
    assert got["admit_named_share"] == pytest.approx(28 / 35)


def test_span_table_counts_seconds_idle_and_bytes():
    red = _red(BUSY, HOST, ["decode", "admit"])
    t = host_spans.span_table(EVENTS, red)
    assert t["engine.launch"]["count"] == 2
    assert t["engine.launch"]["s"] == pytest.approx(3e-9)
    assert t["engine.launch"]["idle_s"] == pytest.approx(3e-9)
    assert t["engine.assemble"]["bucket_bytes"] == 4096
    assert t["engine.assemble"]["stored_bytes"] == 1000
    assert t["store.fetch"]["nbytes"] == 1000


def test_setup_totals_from_recorded_trees():
    tree = Span("engine.step", 0.0, 10.0, children=[
        Span("engine.admit", 0.0, 9.0, children=[
            Span("engine.assemble", 1.0, 3.0, attrs={"bucket_bytes": 8},
                 children=[Span("engine.h2d", 2.0, 3.0, attrs={"nbytes": 8})]),
            Span("engine.write_back", 4.0, 8.0, children=[
                Span("store.put", 5.0, 7.5, children=[Span("store.checksum", 5.0, 7.0)])])])])
    warm = [Span("engine.assemble", 20.0, 20.5)]
    assert host_spans.seconds_in([tree] + warm, "engine.assemble") == pytest.approx(2.5)
    assert host_spans.seconds_in([tree], "store.put") == pytest.approx(2.5)
    assert host_spans.seconds_in(warm, "store.put") is None
    t = host_spans.setup_table([tree])
    assert t["engine.h2d"] == {"count": 1, "s": 1.0, "nbytes": 8}
    assert t["store.checksum"]["s"] == pytest.approx(2.0)


def test_a_trace_without_engine_spans_reads_nothing(view, tmp_path, monkeypatch):  # noqa: F811
    events, window = host_spans.read_host(str(TRACE / "trace.xplane.pb.gz"))
    assert events == ()
    assert window == (view.trace.lo, view.trace.hi)
    pb = tmp_path / "cell" / "plugins" / "profile" / "1" / "t.xplane.pb"
    pb.parent.mkdir(parents=True)
    pb.write_bytes(gzip.decompress((TRACE / "trace.xplane.pb.gz").read_bytes()))
    monkeypatch.setattr(host_spans, "TRACES", tmp_path)
    assert host_spans.events_for(view.trace) == []
    assert runner.reader("decode_host_ms")(view) is None
    assert host_spans.idle_by_span([], view.trace)["idle_s"]
    # the accepted readers read what they read on this trace before the spans
    before = {"prefill_mfu": 4.655494784630451, "decode_mfu": 0.04448187515812419,
              "packed_prefill_roofline": 3.4574424487606294,
              "decode_attention_roofline": 3.320629652527121}
    assert {n: runner.reader(n)(view) for n in before} == pytest.approx(before, rel=1e-12)


@pytest.fixture(scope="module")
def spans_run():
    """The trace recorded with the engine's spans, reduced, and its events."""
    window = _window(SPANS)
    path = str(SPANS / "trace.xplane.pb.gz")
    red = trace.reduce_file(path, [s.kind for s in window.steps if s.in_window])
    events, bounds = host_spans.read_host(path)
    assert bounds == (red.lo, red.hi)
    return red, list(events)


def test_engine_steps_lie_inside_the_harness_steps(spans_run):
    red, events = spans_run
    steps = [h for h in red.host if h.name == "bench.step"]
    inner = [h for h in events if h.name == "engine.step"]
    assert len(inner) == len(steps) > 0
    for outer, h in zip(steps, inner):
        assert outer.start <= h.start < h.end <= outer.end


def test_each_packed_launch_precedes_its_execution(spans_run):
    """The k-th packed launch of the window pairs with the k-th execution of
    the packed prefill program.  The profiler aligns the device's clock to
    the host's only to within a millisecond (on this trace a device program
    shows up to 0.8 ms before the host call that ran it), so an execution
    must start no earlier than that before its launch, after its own
    assembly began, and before the next launch."""
    red, events = spans_run
    launches = [h for h in host_spans.in_window(events, red.lo, red.hi)
                if h.name == "engine.launch" and h.attrs.get("program") == "packed_prefill"]
    assembles = [h for h in host_spans.in_window(events, red.lo, red.hi)
                 if h.name == "engine.assemble"]
    runs = red.executions(PROGRAMS["admit"].name)
    assert len(launches) == len(runs) == len(assembles) > 0
    for k, (launch, asm, ex) in enumerate(zip(launches, assembles, runs)):
        assert asm.start < ex.start and ex.start > launch.start - 1_000_000
        if k + 1 < len(launches):
            assert ex.start < launches[k + 1].start


def test_decode_host_time_and_the_idle_split_on_the_chip_trace(spans_run, tmp_path,
                                                               monkeypatch):
    red, events = spans_run
    split = host_spans.decode_split(events, red, PROGRAMS["decode"].name)
    assert split["steps"] == len(red.executions(PROGRAMS["decode"].name)) > 0
    # a decode step is its host time beside the device plus the program
    assert split["host_ms"] + split["program_ms"] == pytest.approx(split["step_ms"], rel=0.03)
    idle = host_spans.idle_by_span(events, red)
    assert sum(idle["idle_s"].values()) == pytest.approx(red.window_s - red.busy_s, abs=1e-3)
    assert idle["admit_named_share"] >= 0.9
    table = host_spans.span_table(events, red)
    assert table["store.fetch"]["nbytes"] == table["store.checksum"]["nbytes"] > 0
    assert table["engine.assemble"]["stored_bytes"] <= table["engine.assemble"]["bucket_bytes"]
    # the reader finds its run's profile under the traces directory
    pb = tmp_path / "cell" / "plugins" / "profile" / "1" / "t.xplane.pb"
    pb.parent.mkdir(parents=True)
    pb.write_bytes(gzip.decompress((SPANS / "trace.xplane.pb.gz").read_bytes()))
    monkeypatch.setattr(host_spans, "TRACES", tmp_path)
    view = runner.RunView(window=_window(SPANS), dims=None, peak=None, setup_s=0.0, trace=red)
    assert runner.reader("decode_host_ms")(view) == split["host_ms"]
