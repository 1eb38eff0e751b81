"""The reduction from a profiler trace to busy time, idle share, per-program
and per-kernel time, on a small trace recorded on a TPU v5e chip
(``bench/record_trace.py``: the small test configuration served for two
seconds), and on hand-made intervals."""
import dataclasses
import json

import pytest

from bench import flops, harness, match, runner, trace

DATA = harness.REPO / "tests" / "bench" / "data"
TRACE = DATA / "trace"
# the programs of an engine that packs its admissions (harness.programs)
PROGRAMS = {"admit": harness.Program("jit__packed_prefill_impl", per_step=True),
            "decode": harness.Program("jit__decode_impl", per_step=True)}


def test_op_names():
    assert trace.op_name("%decode_attention.4 = bf16[8,2] custom-call(s32[8] %x)") == "decode_attention"
    assert trace.op_name("%bitcast_dynamic-update-slice_fusion.5 = bf16[2]") == \
        "bitcast_dynamic-update-slice_fusion"
    assert trace.op_name("%while.1 = (s32[]) while(...)") == "while"


def test_union_and_gaps():
    busy = trace.union([(5, 10), (0, 3), (8, 12), (20, 30)], 1, 25)
    assert busy == [(1, 3), (5, 12), (20, 25)]
    assert trace.gaps(busy, 0, 26) == [(0, 1), (3, 5), (12, 20), (25, 26)]


def test_self_time_excludes_nested_ops():
    evs = [trace.Event("%while.1 = x", 0, 100), trace.Event("%a.1 = x", 10, 30),
           trace.Event("%b.2 = x", 40, 50), trace.Event("%a.3 = x", 120, 130)]
    assert trace.self_times(evs) == {"while": 70, "a": 30, "b": 10}


def _window():
    raw = json.loads((TRACE / "window.json").read_text())
    raw["reqs"] = [harness.WinReq(**r) for r in raw["reqs"]]
    raw["carried"] = [harness.WinReq(**r) for r in raw.get("carried", [])]
    raw["steps"] = [harness.Step(**dict(s, decoded=[tuple(x) for x in s["decoded"]]
                                        if s["decoded"] else None)) for s in raw["steps"]]
    raw["queue_depth"] = [tuple(x) for x in raw["queue_depth"]]
    return harness.Window(**raw)


@pytest.fixture(scope="module")
def view():
    window = _window()
    red = trace.reduce_file(str(TRACE / "trace.xplane.pb.gz"), [s.kind for s in window.steps
                                                              if s.in_window])
    conf = harness.load_config("small", DATA / "configs")
    model = harness.model_of(conf)
    return runner.RunView(window=window, dims=model.sizes(conf["config"]),
                          peak=flops.peaks("TPU v5 lite"), setup_s=1.0, trace=red,
                          model=model, programs=PROGRAMS)


def test_window_and_busy(view):
    red = view.trace
    assert red is not None
    assert 1.5 < red.window_s < 3.0
    assert 0 < red.busy_s <= red.window_s
    assert red.busy_s <= sum(e.end - e.start for e in red.modules + red.ops) / 1e9
    assert red.busy_s < red.window_s  # the device idled in the window


def test_programs_pair_with_steps(view):
    for kind in ("admit", "decode"):
        got = match.pairs(view, kind)
        assert got, kind
        for step, ex in got:
            assert len(ex) == 1 and ex[0].end - ex[0].start > 0


def test_a_program_the_trace_does_not_show_fails(view):
    with pytest.raises(match.Unpaired):
        renamed = dict(PROGRAMS, admit=harness.Program("jit__renamed_prefill", per_step=True))
        match.pairs(dataclasses.replace(view, programs=renamed), "admit")
    with pytest.raises(match.Unpaired):
        steps = list(view.window.steps)
        steps.remove(next(st for st in steps if st.in_window and st.kind == "admit"))
        runner.reader("prefill_mfu")(dataclasses.replace(
            view, window=dataclasses.replace(view.window, steps=steps)))


def test_kernel_time_inside_its_program(view):
    packed = match.pairs(view, "admit")
    decode = match.pairs(view, "decode")
    assert all(view.trace.kernel_time(ex, "packed_flash_attention") > 0 for _, (ex,) in packed)
    for _, (ex,) in decode:
        t = view.trace.kernel_time(ex, "decode_attention")
        assert 0 < t <= ex.end - ex.start


@pytest.mark.parametrize("name", ["prefill_mfu", "decode_mfu", "packed_prefill_roofline",
                                  "decode_attention_roofline"])
def test_shares_of_peak_are_shares(view, name):
    v = runner.reader(name)(view)
    assert v is not None and 0 < v <= 100


def test_breakdown(view):
    b = view.trace.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(isinstance(n, str) and t > 0 for n, t in b["device_ops"] + b["idle_gaps"])
    gaps = [t for _, t in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert {n for n, _ in b["idle_gaps"]} <= {"step.admit", "step.decode", "step.other",
                                               "wait", "submit", "host.other"}
