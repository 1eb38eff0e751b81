"""One run of one cell: build, fill, warm up, serve open loop, check.

The stages, each a function so that the tests and the calibration tools can
drive them:

1. ``build``: the serving engine through the launcher's pieces
   (``repro.launch.serve.setup``), on one device, with the configuration
   file's engine settings and the weights of ``bench/weights.py``.  What
   is particular to the architecture comes from the configuration's model
   module, ``bench/models/<model>.py`` (``model``).
2. ``fill``: every document the traffic asks more than once admitted once,
   fresh, through the engine's own admission and write-back path (the
   paper's precompute); documents asked once are left unseen.
3. ``warm``: every admission shape that any arrival order of the run's
   requests can form, through the admission program the engine uses
   (packed, or one request at a time), and the decode step, run once
   before the window.
4. ``serve``: the open loop.  It starts with the traffic's lead-in, so
   that the window opens on a loaded engine; only requests that fall due
   inside the window are attempted ones.  A request is submitted when it
   falls due on the wall clock (``arrival_s`` = the engine's own clock, so
   it is admissible at once); every ``engine.step()`` is stamped at its
   return, which is after the engine synced its tokens to the host.  After
   the window no new request is sent and the engine runs until every
   attempted request has finished, or a minute has passed.
5. ``check``: ``bench/correct.py`` on a sample of what was served.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import importlib.util
import itertools
import json
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional


REPO = Path(__file__).resolve().parents[1]
BENCH = REPO / "bench"
SRC = REPO / "src"
CONFIGS = BENCH / "configs"
MODELS = BENCH / "models"

DRAIN_S = 60.0
RID0 = 1_000_000  # request id of the window's first request (the lead-in's lie just below)


def ensure_src() -> None:
    """Put the program's sources on the path; a checkout without them fails."""
    if not (SRC / "repro").is_dir():
        raise FileNotFoundError(f"the serving program's sources are not at {SRC}")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def load_config(name: str, directory: Path = CONFIGS) -> dict:
    return json.loads((directory / f"{name}.json").read_text())


# --------------------------------------------------------------------------- #
# 1. build
# --------------------------------------------------------------------------- #
@functools.lru_cache(maxsize=None)
def model(name: str, directory: Path = MODELS):
    """The model module ``<directory>/<name>.py``, loaded once per process:
    the architecture's sizes, program fields, weight tree, plain reference
    and counts (``bench/models/dense_gqa.py`` says what each is)."""
    path = directory / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no model module {path}")
    spec = importlib.util.spec_from_file_location(f"bench_model_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # its dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


def model_of(conf: dict, directory: Path = MODELS):
    """The model module the configuration file names (``"model"``)."""
    return model(conf["model"], directory)


def program_config(conf: dict, models_dir: Path = MODELS):
    """The program's ArchConfig for this configuration file, checked against
    the fields its model module derives from the file's sizes, so that a
    drift of the program's configs shows."""
    from repro.configs import get_config

    p = conf["program"]
    cfg = dataclasses.replace(get_config(p["arch"]), **p.get("replace", {}))
    want = model_of(conf, models_dir).program_fields(conf["config"])
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise ValueError(f"program config differs from {conf['name']}: {bad}")
    return cfg


def check_tree(cfg, weights) -> None:
    """The weights must have the program's parameter tree, leaf for leaf."""
    import jax
    import jax.numpy as jnp
    from repro.models import registry

    api = registry.get_model(cfg)
    want = jax.eval_shape(lambda k: api.init(k, cfg), jax.ShapeDtypeStruct((2,), jnp.uint32))
    sig = lambda t: jax.tree_util.tree_map(lambda a: (tuple(a.shape), str(a.dtype)), t)
    if sig(want) != sig(weights):
        raise ValueError("the program's parameter tree differs from the model module's")


def build(conf: dict, seed: int, device, weights=None, models_dir: Path = MODELS):
    """(engine, dims): the engine on ``device``, weights from ``seed``;
    ``dims`` are the sizes of the configuration's model module."""
    import jax
    from bench import weights as bench_weights
    from repro.launch import serve as launch
    from repro.serving import ServingEngine

    e = conf["engine"]
    argv = ["--arch", conf["program"]["arch"], "--no-reduced",
            "--platform", e["platform"], "--slots", str(e["slots"]),
            "--policy", e["policy"]]
    s = launch.setup(launch.parse_args(argv), **e.get("overrides", {}))
    cfg = program_config(conf, models_dir)
    s = dataclasses.replace(s, cfg=cfg)
    mod = model_of(conf, models_dir)
    dims = mod.sizes(conf["config"])
    if weights is None:
        weights = bench_weights.make(mod, dims, seed, device)
    check_tree(cfg, weights)
    engine = ServingEngine(
        cfg, weights, engine_cfg=s.engine_cfg, planner=s.planner_factory(),
        pricing=s.pricing, perf=s.perf, device=device,
    )
    jax.block_until_ready(weights)
    return engine, dims


# --------------------------------------------------------------------------- #
# 2. fill
# --------------------------------------------------------------------------- #
def run_until_idle(engine) -> List:
    events = []
    while not engine.idle:
        events.extend(engine.step())
    return events


def asks(traffic) -> List[int]:
    """Per document, how many of the run's requests ask it."""
    n = [0] * len(traffic.docs)
    for r in traffic.requests:
        n[r.doc] += 1
    return n


def fill(engine, traffic) -> List[int]:
    """Admit once, fresh, one at a time, every document the traffic asks
    more than once; the engine's planner writes each back.  A document
    asked once is left unseen: its request pays the whole prefill.  The
    first document admitted asks for two tokens, so that the decode step
    runs too.  Returns, per document, the tokens the store now matches (its
    whole chunks: the store hashes whole chunks of tokens; 0 where the
    document was left unseen)."""
    from repro.serving.request import Request

    stored = [i for i, n in enumerate(asks(traffic)) if n > 1]
    for i in stored:
        engine.submit(Request(
            req_id=i, context_tokens=traffic.docs[i], prompt_tokens=traffic.fill_prompts[i],
            max_new_tokens=2 if i == stored[0] else 1, arrival_s=engine.clock.now,
            expected_reuses=traffic.expected_reuses,
        ))
        run_until_idle(engine)
    matched = [0] * len(traffic.docs)
    for i in stored:
        doc = traffic.docs[i]
        match, entry = engine.store.lookup(doc)
        if entry is None or match.matched_tokens <= 0:
            raise RuntimeError(f"document {i} ({len(doc)} tokens) was not stored by the fill")
        matched[i] = match.matched_tokens
    return matched


# --------------------------------------------------------------------------- #
# 3. warm
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class Program:
    """A jitted program of the engine as the trace names it (``jit_`` and
    the function's name), and whether each step of its kind runs it once
    (``per_step``) or as many times as the step launches it."""

    name: str
    per_step: bool


def programs(engine) -> Dict[str, Program]:
    """The programs of the engine's admission and decode steps: the packed
    prefill where the engine packs its admissions, else its per-request
    prefill (one launch, or two where the context is written back between
    them)."""
    def name(fn) -> str:
        return f"jit_{fn.__name__}"

    decode = Program(name(engine._jit_decode), per_step=True)
    if getattr(engine, "_packable", False) and getattr(engine, "_jit_packed", None) is not None:
        return {"admit": Program(name(engine._jit_packed), per_step=True), "decode": decode}
    return {"admit": Program(name(engine._jit_prefill), per_step=False), "decode": decode}


def request_sizes(traffic, matched: List[int]) -> List[tuple]:
    """The distinct (matched, n_new, context) of the run's requests."""
    return sorted({(matched[r.doc], len(traffic.docs[r.doc]) - matched[r.doc] + len(r.question),
                    len(traffic.docs[r.doc])) for r in traffic.requests})


def admission_sets(engine, traffic, matched: List[int]) -> List[List]:
    """Every batch that any arrival order can admit: each multiset of up to
    ``admit_batch`` of the run's request sizes (admission takes the queue's
    head, and the seed draws the order).  Each is (matched, n_new) per
    request, ``matched[d]`` being what the store holds of document d."""
    k_max = min(engine.ec.admit_batch or engine.ec.max_slots, engine.ec.max_slots)
    sizes = sorted({(m, n) for m, n, _ in request_sizes(traffic, matched)})
    return [list(c) for k in range(1, k_max + 1)
            for c in itertools.combinations_with_replacement(sizes, k)]


def warm(engine, traffic, matched: List[int],
         log: Callable[[str], None] = print) -> Dict[str, int]:
    """Run, before the window, the device work of every admission shape any
    arrival order can form, through the admission program the engine uses
    (``programs``), then one decode step.  The program compiles these per
    shape; this replays the engine's own calls on zero inputs and drops
    what they return.  An engine without those calls is an error: the
    window would compile them."""
    import jax
    import jax.numpy as jnp

    need = ("_jit_decode", "_state")
    if not all(hasattr(engine, a) for a in need) or engine._state is None:
        raise RuntimeError(f"warm-up: the engine has no {need}; its admission shapes cannot be warmed")
    with jax.default_device(engine.device):
        if programs(engine)["admit"].per_step:
            out = _warm_packed(engine, traffic, matched)
        else:
            out = _warm_single(engine, traffic, matched)
        # one decode step with every slot inactive: positions stay, and the
        # rows it writes lie past every slot's valid length
        n = engine.ec.max_slots
        logits, engine._state = engine._jit_decode(
            engine.params, jnp.zeros((n, 1), jnp.int32), engine._state, jnp.zeros((n,), bool))
        jnp.argmax(logits, axis=-1).block_until_ready()
    return out


def _warm_packed(engine, traffic, matched: List[int]) -> Dict[str, int]:
    """The packed launch of each (q, kv) bucket pair, and the per-request
    slicing and slot insertion at each length: ``_packed_launch``,
    ``paged.packed_to_artifact`` (also at the context's length where a fresh
    context may be written back) and ``paged.insert_slot``."""
    import jax
    import jax.numpy as jnp
    from repro.kvcache import paged

    if not hasattr(engine, "_packed_launch"):
        raise RuntimeError("warm-up: the engine has no _packed_launch; its admission shapes "
                           "cannot be warmed")
    ec, cfg = engine.ec, engine.cfg
    # a fresh segment of n_new tokens may write back its first `context` rows
    written: Dict[int, set] = {}
    for m, n_new, ctx in request_sizes(traffic, matched):
        if m == 0:
            written.setdefault(n_new, set()).add(ctx)
    by_pair: Dict[tuple, list] = {}
    for run in admission_sets(engine, traffic, matched):
        layout = paged.pack_layout(
            list(range(len(run))), [m for m, _ in run], [n for _, n in run],
            align=ec.pack_align, bucket_min=ec.pack_bucket_min)
        by_pair.setdefault((layout.q_len, layout.kv_len), []).append(layout)
    sliced, inserted = set(), set()
    for (q_len, kv_len), layouts in sorted(by_pair.items()):
        first = layouts[0]
        logits, caches = engine._packed_launch(
            first, [[0] * s.n_new for s in first.segments], [None] * len(first.segments))
        jnp.argmax(logits[0]).block_until_ready()
        for layout in layouts:
            for seg in layout.segments:
                lengths = [seg.n_total] + sorted(written.get(seg.n_new, ()) if seg.matched == 0
                                                 else ())
                for n in lengths:
                    if (kv_len, n) in sliced:
                        continue
                    sliced.add((kv_len, n))
                    art = paged.packed_to_artifact(cfg, caches, seg, n)
                    if n == seg.n_total and n not in inserted:
                        inserted.add(n)
                        out = paged.insert_slot(cfg, engine._state, 0, art)
                        jax.block_until_ready(out)
                        del out
                    else:
                        jax.block_until_ready(art)
                    del art
        del logits, caches
    return {"pairs": len(by_pair), "lengths": len(inserted), "slices": len(sliced)}


def _warm_single(engine, traffic, matched: List[int]) -> Dict[str, int]:
    """Each request's launches on the per-request path (``_jit_prefill`` on
    a batch-1 state, one program per token length): the context and
    question in one launch; the context alone, its artifact
    (``paged.extract_slot``) and then the question, where the context is
    written back; where the store holds part of it, the stored rows
    inserted (``paged.insert_slot``) and the rest prefilled.  Each with the
    first token's argmax and the landing of the state in a slot, at each
    request's total length."""
    import jax
    import jax.numpy as jnp
    from repro.kvcache import paged

    cfg, ec = engine.cfg, engine.ec
    launched, extracted, inserted, landed = set(), set(), set(), set()

    def prefill(n):
        launched.add(n)
        state = engine.api.init_state(cfg, 1, ec.max_len)
        tokens = jnp.asarray([[0] * n], jnp.int32)  # as the engine makes them
        logits, state = engine._jit_prefill(engine.params, tokens, state)
        int(jnp.argmax(logits[0]))
        return state

    for m, n_new, ctx in request_sizes(traffic, matched):
        total = m + n_new
        for n in sorted({total - ctx, n_new} - launched):
            prefill(n)
        if total not in landed:
            landed.add(total)
            jax.block_until_ready(paged.insert_slot(cfg, engine._state, 0, prefill(total)))
        if ctx not in extracted or (m and m not in inserted):
            extracted.add(ctx)
            art = paged.extract_slot(cfg, prefill(ctx), 0, ctx)
            if m and m not in inserted:
                inserted.add(m)
                fresh = engine.api.init_state(cfg, 1, ec.max_len)
                jax.block_until_ready(paged.insert_slot(cfg, fresh, 0, art, n_tokens=m))
    return {"lengths": len(launched), "extracted": len(extracted), "inserted": len(inserted),
            "landed": len(landed)}


# --------------------------------------------------------------------------- #
# 4. serve
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class WinReq:
    """One request as the harness saw it (wall-clock seconds): an attempted
    one, or one of the lead-in (``lead``)."""

    rid: int
    due: float
    submitted: float
    ctx_len: int
    q_len: int
    max_new: int
    admit_began: Optional[float] = None  # start of the step that admitted it
    admitted: Optional[float] = None  # return of that step
    token_times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    matched: int = 0
    finished: bool = False
    finished_at: Optional[float] = None
    lead: bool = False

    @property
    def first_token(self) -> Optional[float]:
        return self.token_times[0] if self.token_times else None


@dataclasses.dataclass
class Step:
    """One ``engine.step()``: wall-clock start and return, what it did."""

    t0: float
    t1: float
    kind: str  # "admit" | "decode" | "other"
    in_window: bool
    batch: Optional[List[int]] = None  # request ids admitted, in pack order
    decoded: Optional[List[tuple]] = None  # (rid, token index) emitted


@dataclasses.dataclass
class Window:
    start: float
    end: float
    reqs: List[WinReq]  # the attempted requests: those due inside the window
    steps: List[Step]
    generator_late: List[float]  # per request: submitted - due
    queue_depth: List[tuple]  # (wall time, requests queued or in a slot)
    run_end: float  # when the drain ended
    carried: List[WinReq] = dataclasses.field(default_factory=list)  # the lead-in's
    written_back: List[tuple] = dataclasses.field(default_factory=list)  # (wall time, bytes)

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def served(self) -> List[WinReq]:
        """Every request the run sent: the lead-in's, then the attempted."""
        return self.carried + self.reqs

    def by_rid(self) -> Dict[int, WinReq]:
        return {r.rid: r for r in self.served}


def _annotation(on: bool, name: str):
    """``jax.profiler.TraceAnnotation`` when tracing, else nothing."""
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def serve(engine, traffic, seconds: float, annotate: bool = False,
          drain_s: float = DRAIN_S, on_window_start: Optional[Callable[[], None]] = None,
          on_window_end: Optional[Callable[[], None]] = None) -> Window:
    """The lead-in, the open loop over ``seconds``, then the drain.
    ``on_window_start`` runs between steps once the window has opened (the
    trace starts there), ``on_window_end`` when it closes, before the drain
    (the trace stops there)."""
    from repro.serving import events as ev
    from repro.serving.request import Request

    ann = functools.partial(_annotation, annotate)
    reqs: Dict[int, WinReq] = {}
    steps: List[Step] = []
    late: List[float] = []
    depth: List[tuple] = []
    written: List[tuple] = []
    pending = list(traffic.requests)
    nxt = 0
    lead_s = max(0.0, -min((r.due_s for r in pending), default=0.0))
    start = time.perf_counter() + lead_s
    end = start + seconds
    phase = "lead"  # -> "window" -> "drain"
    deadline = end + drain_s
    while True:
        now = time.perf_counter()
        if phase == "lead" and now >= start:
            phase = "window"
            if on_window_start is not None:
                on_window_start()
        if phase == "window" and now >= end:
            phase = "drain"
            if on_window_end is not None:
                on_window_end()
        if phase != "drain":
            with ann("bench.submit"):
                while nxt < len(pending) and start + pending[nxt].due_s <= now:
                    r = pending[nxt]
                    rid = RID0 + r.idx
                    engine.submit(Request(
                        req_id=rid, context_tokens=traffic.docs[r.doc],
                        prompt_tokens=r.question, max_new_tokens=r.max_new_tokens,
                        arrival_s=engine.clock.now,
                        expected_reuses=traffic.expected_reuses,
                    ))
                    t_sub = time.perf_counter()
                    due = start + r.due_s
                    reqs[rid] = WinReq(rid=rid, due=due, submitted=t_sub,
                                       ctx_len=len(traffic.docs[r.doc]),
                                       q_len=len(r.question), max_new=r.max_new_tokens,
                                       lead=r.due_s < 0)
                    if r.due_s >= 0:
                        late.append(t_sub - due)
                    nxt += 1
        elif all(r.finished for r in reqs.values()) or now >= deadline:
            break
        if engine.idle:
            if phase == "drain":
                break
            wake = min(start if phase == "lead" else end,
                       start + pending[nxt].due_s if nxt < len(pending) else end)
            with ann("bench.wait"):
                time.sleep(max(0.0, wake - time.perf_counter()))
            continue
        t0 = time.perf_counter()
        with ann("bench.step"):
            events = engine.step()
        t1 = time.perf_counter()
        step = Step(t0=t0, t1=t1, kind="other", in_window=phase == "window")
        single = []  # requests admitted one at a time (no BatchAdmitted)
        for e in events:
            r = reqs.get(e.req_id)
            if isinstance(e, ev.BatchAdmitted):
                step.kind, step.batch = "admit", list(e.req_ids)
            elif isinstance(e, ev.StoreWriteBack):
                written.append((t1, e.nbytes))
            elif r is None:
                continue
            elif isinstance(e, ev.RequestAdmitted):
                r.admit_began, r.admitted = t0, t1
                single.append(r.rid)
            elif isinstance(e, ev.KVLoaded):
                r.matched = e.matched_tokens
            elif isinstance(e, ev.TokenEmitted):
                r.token_times.append(t1)
                r.tokens.append(int(e.token))
                if e.index > 0:
                    step.kind = "decode"
                    step.decoded = (step.decoded or []) + [(r.rid, e.index)]
            elif isinstance(e, ev.RequestFinished):
                r.finished, r.finished_at = True, t1
        if single and step.batch is None:
            step.kind, step.batch = "admit", single
        steps.append(step)
        depth.append((t1, engine.load()))
    every = sorted(reqs.values(), key=lambda r: r.rid)
    return Window(start=start, end=end, reqs=[r for r in every if not r.lead], steps=steps,
                  generator_late=late, queue_depth=depth, run_end=time.perf_counter(),
                  carried=[r for r in every if r.lead], written_back=written)


# --------------------------------------------------------------------------- #
# 5. free, for the check
# --------------------------------------------------------------------------- #
def free_device(keep=None) -> None:
    """Drop every array still on the device (after the engine is deleted),
    but those of the pytree ``keep``."""
    import jax

    gc.collect()
    kept = {id(a) for a in jax.tree_util.tree_leaves(keep)}
    for a in jax.live_arrays():
        if id(a) not in kept:
            a.delete()
    gc.collect()
