"""Host spans of the serving path (``repro.obs.host``): what the recorder
keeps, how spans nest, and that they change nothing the engine computes.

A stored context is written back by one packed admission and reused by a
second one; each is followed by a dense decode step.  The paged, unified
and fused paths take the same names at the same boundaries."""
import glob

import jax
import jax.monitoring
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.models import registry
from repro.obs import chrome_trace, host
from repro.serving import (
    AlwaysReusePlanner,
    BlendPlanner,
    EngineConfig,
    Request,
    ServingEngine,
)

CHUNK = 16


@pytest.fixture(scope="module")
def small():
    cfg = reduced_config(get_config("llama-7b"))
    params = registry.get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(autouse=True)
def recorder_off():
    host.stop()
    yield
    host.stop()


def _requests(cfg):
    """Request 0 stores its context; request 1, due later, reuses it."""
    rng = np.random.default_rng(0)
    ctx = list(map(int, rng.integers(0, cfg.vocab, 4 * CHUNK)))
    return [Request(req_id=i, context_tokens=ctx,
                    prompt_tokens=list(map(int, rng.integers(0, cfg.vocab, 8))),
                    max_new_tokens=2, arrival_s=30.0 * i)
            for i in range(2)]


def _shuffled(cfg):
    """Request 0 stores four chunks; requests 1-2 ask for them reordered."""
    rng = np.random.default_rng(4)
    pool = [list(map(int, rng.integers(0, cfg.vocab, CHUNK))) for _ in range(4)]
    out = []
    for i, p in enumerate(([0, 1, 2, 3], [2, 0, 3, 1], [3, 2, 1, 0])):
        out.append(Request(req_id=i, context_tokens=sum((pool[j] for j in p), []),
                           prompt_tokens=list(map(int, rng.integers(0, cfg.vocab, 8))),
                           max_new_tokens=2, arrival_s=30.0 * i, expected_reuses=4))
    return out


PATHS = {
    "packed_dense": (dict(), AlwaysReusePlanner, _requests),
    "paged": (dict(paged_decode=True), AlwaysReusePlanner, _requests),
    "unified": (dict(paged_decode=True, unified_step=True), AlwaysReusePlanner, _requests),
    "fused": (dict(fusion_enabled=True),
              lambda: BlendPlanner(recompute_frac=0.25, always=True), _shuffled),
}


def _serve(cfg, params, path="packed_dense"):
    kw, planner, reqs = PATHS[path]
    eng = ServingEngine(cfg, params, planner=planner(), engine_cfg=EngineConfig(
        max_slots=2, max_len=128, chunk_tokens=CHUNK, **kw))
    for r in reqs(cfg):
        eng.submit(r)
    while not eng.idle:
        eng.step()
    return eng, {r.req_id: tuple(r.tokens) for r in eng.records}


def _shape(s):
    return (s.name, tuple(_shape(c) for c in s.children))


def _names(spans):
    return {s.name for root in spans for s in root.walk()}


def test_recorder_off_keeps_nothing_and_serves_the_same_tokens(small):
    cfg, params = small
    _, off = _serve(*small)
    assert host.take() == [] and host.dropped() == 0
    host.start()
    _, on = _serve(*small)
    assert host.take()
    assert on == off


def test_admission_and_decode_spans_nest_as_documented(small):
    host.start()
    _serve(*small)
    steps = host.take()
    assert host.dropped() == 0
    assert all(s.name == "engine.step" for s in steps)
    busy = [s for s in steps if s.children]
    fresh, decode, reused = busy[0], busy[1], busy[2]
    lookup = ("engine.plan", (("store.lookup", ()),))
    assemble = ("engine.assemble", (("engine.h2d", ()), ("engine.h2d", ())))
    launch = ("engine.launch", ())
    assert _shape(fresh) == ("engine.step", (("engine.admit", (
        lookup, assemble, launch,
        ("engine.write_back", (("engine.d2h", ()),
                               ("store.put", (("store.checksum", ()),)))),
        ("engine.land", ()), ("engine.sync", ()))),))
    assert _shape(decode) == ("engine.step", (("engine.decode", (
        launch, ("engine.sync", ()), ("engine.emit", ()))),))
    assert _shape(reused) == ("engine.step", (("engine.admit", (
        lookup, ("store.fetch", (("store.checksum", ()),)), assemble, launch,
        ("engine.land", ()), ("engine.sync", ()))),))
    # per-request ids, batch spans carrying theirs, and the counters
    admit = reused.children[0]
    assert admit.attrs == {"n": 1, "req_ids": "1"}
    plan, fetch, asm, lau, land, _ = admit.children
    assert plan.req_id == plan.children[0].req_id == fetch.req_id == land.req_id == 1
    assert plan.children[0].attrs["matched_tokens"] == 4 * CHUNK
    assert fetch.attrs["tier"] == "io2" and fetch.attrs["nbytes"] > 0
    assert fetch.children[0].attrs["nbytes"] > 0
    assert 0 < asm.attrs["stored_bytes"] < asm.attrs["bucket_bytes"]
    assert lau.attrs["program"] == "packed_prefill"
    assert lau.attrs["kv_len"] == asm.attrs["kv_len"] and lau.attrs["jit_hit"] in (0, 1)
    wb = fresh.children[0].children[3]
    assert wb.req_id == 0 and wb.attrs["nbytes"] == wb.children[0].attrs["nbytes"] > 0
    assert wb.children[1].attrs["tier"] == "io2"
    assert decode.children[0].attrs == {"n_active": 1}
    assert decode.children[0].children[2].attrs == {"n_tokens": 1}
    for root in steps:  # measured host seconds, nested in time
        for s in root.walk():
            assert all(s.start_s <= c.start_s <= c.end_s <= s.end_s for c in s.children)
            assert s.replica == host.HOST_PID


@pytest.mark.parametrize("path", ["paged", "unified", "fused"])
def test_other_paths_take_the_same_names(small, path):
    _, off = _serve(*small, path=path)
    host.start()
    _, on = _serve(*small, path=path)
    names = _names(host.take())
    assert on == off
    want = {"engine.step", "engine.admit", "engine.plan", "store.lookup", "store.fetch",
            "store.checksum", "store.put", "engine.write_back", "engine.d2h",
            "engine.launch", "engine.sync", "engine.decode", "engine.emit", "engine.land"}
    if path != "unified":
        want |= {"engine.assemble", "engine.h2d"}
    assert want <= names
    assert {n.split(".")[0] for n in names} == {"engine", "store"}


def test_token_identity_and_zero_extra_compiles(small):
    cfg, params = small
    compiles = []

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    def run():
        before = len(compiles)
        eng, toks = _serve(cfg, params)
        return toks, eng.jit_stats.misses, len(compiles) - before

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        run()  # every program compiled once
        tok_off, miss_off, comp_off = run()
        host.start()
        tok_on, miss_on, comp_on = run()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert host.take()
    assert tok_on == tok_off
    assert miss_on == miss_off
    assert comp_on == comp_off


def test_spans_land_on_the_profilers_host_plane(small, tmp_path):
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        _serve(*small)
    (pb,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = {}
    for plane in ProfileData.from_file(pb).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("engine.", "store.")):
                        events.setdefault(e.name, dict(e.stats))
    assert {"engine.step", "engine.admit", "engine.assemble", "engine.launch",
            "engine.decode", "store.fetch", "store.checksum", "store.put"} <= set(events)
    assert events["store.fetch"]["tier"] == "io2"
    assert str(events["engine.admit"]["req_ids"]) in ("0", "1")  # the profiler reads "0" as 0
    assert events["engine.launch"]["program"] in ("packed_prefill", "decode")


def test_recorder_drops_and_counts_past_its_bound():
    host.start(limit=3)
    with host.span("a", req=5) as a:
        with host.span("b"):
            pass
        with host.span("c"):
            with host.span("d"):  # past the bound, as is its child
                with host.span("e"):
                    pass
        a.set(n=2)
    with host.span("f"):
        pass
    (root,) = host.take()
    assert _shape(root) == ("a", (("b", ()), ("c", ())))
    assert root.req_id == 5 and root.attrs == {"n": 2}
    assert host.dropped() == 3
    with host.span("g"):  # room again after the take
        pass
    assert [s.name for s in host.take()] == ["g"]


def test_host_spans_export_on_their_own_process_track():
    host.start()
    with host.span("engine.step"):
        with host.span("engine.plan", req=3):
            pass
    trace = chrome_trace(host.take())["traceEvents"]
    (meta,) = [e for e in trace if e["ph"] == "M"]
    assert meta["pid"] == host.HOST_PID and "host" in meta["args"]["name"]
    assert {(e["name"], e["tid"]) for e in trace if e["ph"] == "X"} == {
        ("engine.step", 0), ("engine.plan", 4)}


def test_recorder_counts_hold_across_threads():
    import sys
    import threading

    n_threads, n_spans, limit = 16, 300, 5000
    host.start(limit=limit)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(n_spans):
                with host.span("engine.step"):
                    with host.span("engine.decode"):
                        pass

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    roots = host.take()
    kept = sum(1 for r in roots for _ in r.walk())
    assert kept == limit
    assert kept + host.dropped() == 2 * n_threads * n_spans
    assert all(len(r.children) <= 1 and r.name == "engine.step" for r in roots)
