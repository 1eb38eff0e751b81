"""Continuous-batching serving engine, structured as plan -> execute.

The paper's pipeline, end to end: on admission a request's context is looked
up in the tiered ContextStore (chain-hash prefix match); a pluggable
``ReusePlanner`` turns (request, lookup, workload) into a declarative
``ReusePlan`` (recompute / load / partial-load, + write-back); the engine
*executes* the plan — storage fetch through the tier's ``StorageBackend``,
(suffix-)prefill of the unmatched tail + prompt, break-even-gated write-back
— and decode runs batched across slots.

The engine is step-driven: ``submit()`` enqueues, ``step()`` performs one
scheduling step (admit a batch of requests, or one batched decode step, or a
clock jump to the next arrival) and returns the typed ``events`` it produced;
``drain()`` iterates steps to completion; ``run()`` is the thin
drain-then-summarize loop.  Traces, streaming callers, and the benchmarks
all drive this one surface.

Admission is *batched and packed*: every admissible request with a free slot
is planned individually (lookup -> ReusePlan), then all unmatched context
tails + prompts execute as ONE packed ragged suffix-prefill — token runs
concatenated into a single sequence, segment ids keeping cross-request
attention masked out (``kernels/packed_prefill.py``), outputs scattered back
into per-slot paged state.  Packed lengths round up to power-of-two jit
buckets so steady traffic reuses compiled kernels (``packed_stats()`` exposes
the hit/miss counters); with ``admit_batch=1`` the packed path reproduces
per-request admission numerics and timing exactly (golden-parity tested).

Time/cost accounting: compute is real JAX execution with *modeled* durations
(PerfModel — this container has no TPU), storage/network delays flow through
the backends' TransferModel.  Numerics are real: reused-KV outputs are
bit-comparable to recompute outputs (tests/test_serving.py asserts it).
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.cost_model import Workload, s_storage_bytes
from repro.core.perf_model import PerfModel, tpu_v5e
from repro.core.pricing import Pricing, tpu_v5e_pod
from repro.kvcache import fusion, paged
from repro.kvcache.backend import StorageBackend
from repro.kvcache.faults import FaultInjector, RetryPolicy, StorageError
from repro.kvcache.hierarchy import (
    BreakEvenMigrator,
    TieredStore,
    TierSpec,
    build_backends,
)
from repro.kvcache.transfer import SimClock, TransferModel
from repro.models import common as model_common
from repro.models import registry
from repro.obs.host import span
from repro.serving import events as ev
from repro.serving import metrics as metrics_mod
from repro.serving.planner import (
    CostAwarePlanner,
    ReusePlan,
    ReusePlanner,
    StoreLookup,
)
from repro.serving.jit_cache import JitBucketStats
from repro.serving.request import Request, RequestRecord, Slot
from repro.serving.scheduler import AdmissionQueue, HedgePolicy


@dataclasses.dataclass
class EngineConfig:
    max_slots: int = 4
    max_len: int = 512
    chunk_tokens: int = 16
    reuse_enabled: bool = True
    tier_capacities_gb: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {"host_dram": 64.0, "io2": 1024.0}
    )
    # Full hierarchy declaration (fastest first); overrides tier_capacities_gb
    # and enables per-tier backend kinds + link concurrency limits.
    tier_specs: Optional[List[TierSpec]] = None
    # Tier write-backs land in (default: the last/cheapest tier).
    store_tier: Optional[str] = None
    # >0 enables the clock-driven break-even migration pass at this cadence;
    # migrations surface as TierMigrated events.
    migration_interval_s: float = 0.0
    migration_policy: Optional[BreakEvenMigrator] = None
    # Under capacity pressure, demote the least valuable entry one tier down
    # instead of deleting it outright.
    spill_on_pressure: bool = False
    compress_tier: Optional[str] = None  # e.g. "io2" for the int8 tier
    overlap_load: bool = False  # beyond-paper prefetch overlap
    hedge: Optional[HedgePolicy] = None
    eviction: str = "cost"
    store_write_back: bool = True
    # Economics-at-scale: model times/costs (prefill, decode, KV bytes) as if
    # serving this FULL arch while the actual compute uses a reduced config —
    # functional tests and CPU examples get paper-scale $ and delays with
    # real token-level numerics. None = model the served config itself.
    cost_arch: Optional[str] = None
    # Lookahead prefetch (beyond-paper): when admitting a request, start
    # fetching the stored contexts of the next queued requests so their loads
    # overlap the current request's compute.  The paper's pipeline loads
    # at admission (TTFT pays the full fetch); with lookahead only the
    # not-yet-arrived remainder shows up in TTFT.
    prefetch_lookahead: int = 0
    # Max requests admitted per step as one packed ragged prefill (None =
    # every admissible request with a free slot).  1 reproduces per-request
    # admission timing exactly (the serve_bench baseline).
    admit_batch: Optional[int] = None
    # Each segment's kv span starts at a multiple of this (the flash kernel's
    # kv block): cross-segment kv blocks become fully-masked exact no-ops,
    # which is what makes packed outputs bit-identical to per-request ones.
    pack_align: int = 128
    # Smallest jit bucket for the packed q length (lengths round up to the
    # next power of two so steady-state serving stops recompiling).
    pack_bucket_min: int = 16
    # Paged batched decode: all active slots decode in ONE launch that
    # gathers each slot's live kv_block-token blocks from a shared block
    # pool (kernels/paged_decode.py) instead of streaming a dense per-slot
    # cache padded to max_len; the step is priced on the live blocks
    # (PerfModel.t_decode_paged).  Packed-prefill outputs land directly in
    # the pool (segments are kv_block-aligned, so spans ARE whole blocks);
    # batch-mates that loaded the same stored context share its full prefix
    # blocks (refcounted, copy-on-write on append).  Requires a packable
    # arch; others silently keep the dense path.  Tokens are bit-identical
    # to dense decode either way (tests/test_paged_decode.py).
    paged_decode: bool = False
    # Pool block size in tokens; must equal pack_align so packed-prefill kv
    # spans land block-aligned in the pool.
    kv_block: int = 128
    # CacheBlend-style fused non-prefix reuse: consult the store's chunk-
    # content index at lookup time (StoreLookup.composite) so a BlendPlanner
    # can plan "fused" admissions — assemble stored chunk KV out of order and
    # selectively recompute only its planner-chosen r-fraction
    # (kvcache/fusion.py + kernels/fused_prefill.py).  Off by default: the
    # seed golden trace replays untouched, and non-Blend planners ignore the
    # composite field entirely.  Packable attention archs only (assembled KV
    # needs per-position state); others never see a composite match.
    fusion_enabled: bool = False
    # Unified continuous-batching step (Sarathi-style chunked prefill): one
    # launch per step whose rows mix in-flight decode tokens with kv_block-
    # wide chunks of pending suffix-prefills, all over the shared block pool
    # (kernels/chunked_prefill.py).  Admissions stop monopolizing the device:
    # a long prefill lands incrementally while decodes keep stepping, so
    # burst arrivals no longer spike in-flight decode token gaps.  Requires
    # paged_decode and a packable arch; off by default — the seed golden
    # trace replays untouched (serve_bench's unified lane flips it on).
    unified_step: bool = False
    # Per-launch q-token quota for the unified step: decode rows always ride
    # (one token each), the remainder is granted to ready prefill chunks in
    # slot order.  Bounds the compute any single step can add on top of pure
    # decode — the knob behind the flat-decode-p99 CI gate.  160 keeps a
    # fully-granted mixed launch within ~1.17x of a pure decode step under
    # the default TPU-v5e(8) cost model (the gate's envelope is 1.2x);
    # compute-poorer hardware needs a smaller budget — serve_bench's unified
    # lane solves for it against its own PerfModel (_flat_step_budget).
    step_token_budget: int = 160
    # Seeded fault injection (kvcache/faults.FaultInjector): every storage
    # backend consults it for transient failures / brownouts / corruption,
    # and a ServingCluster for scheduled replica crashes.  None (default) =
    # no injection; the engine still verifies put/get checksums.
    faults: Optional[FaultInjector] = None
    # Cost-aware retry applied when a planned fetch fails (exponential
    # backoff; retries only while expected retry $ beats marginal recompute
    # $).  None = RetryPolicy() defaults.
    retry_policy: Optional[RetryPolicy] = None
    # Min-cacheable-size admission (the production prompt-cache rule from
    # SNIPPETS.md): contexts shorter than this many tokens are never written
    # back — a tiny entry's storage + write overhead can't repay itself.  0
    # (default) keeps the existing chunk_tokens floor and golden parity.
    min_cache_tokens: int = 0


@dataclasses.dataclass
class _Admission:
    """One request's admission in flight: plan phase fills the first five
    fields, packed execution the rest."""

    req: Request
    rec: RequestRecord
    slot: Slot
    plan: ReusePlan
    lookup: StoreLookup
    artifact: Any = None  # fetched stored state (None = recompute)
    delay: float = 0.0  # raw storage fetch delay
    load_s: float = 0.0  # delay actually charged (post-overlap)
    nbytes: float = 0.0
    matched: int = 0
    new_tokens: List[int] = dataclasses.field(default_factory=list)
    # fused admissions: source entries pinned between plan and execute (a
    # batch-mate's write-back pressure must not evict a fusion source)
    pins: List[str] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _ChunkStream:
    """One admission's pending suffix-prefill under the unified step: the
    q-token stream still to land (context tail + prompt; for fused plans the
    recompute spans + prompt) with each token's absolute target position.
    The slot's pool blocks are fully admitted up front; chunks of up to
    kv_block tokens land per unified launch until the stream drains, at
    which point the first generated token is emitted and the slot activates
    for decode."""

    a: _Admission
    tokens: np.ndarray  # int32 [n_q] q tokens still to prefill
    positions: np.ndarray  # int32 [n_q] absolute positions, increasing
    n_ctx: int  # context length (write-back row count)
    ready_s: float  # clock time the storage fetch completes
    store_after: bool = False  # write context rows back on completion
    done: int = 0  # tokens already landed

    @property
    def remaining(self) -> int:
        return len(self.tokens) - self.done


# Host spans (``obs.host``): measured host time of each boundary of the
# serving path, on the profiler's clock; docs/OBSERVABILITY.md has the table.
def _ids(reqs) -> str:
    """A batch span's ``req_ids``: space-separated (a profiler annotation
    splits its metadata at commas)."""
    return " ".join(str(r.req_id) for r in reqs)


def _first_token(logits_row: jax.Array) -> int:
    """The greedy token of one logits row, synced to the host."""
    with span("engine.sync"):
        return int(jnp.argmax(logits_row))


def _to_host(make, parent: span) -> Any:
    """The artifact ``make()`` gathers on the device, copied to host numpy;
    its bytes label ``parent`` too."""
    with span("engine.d2h") as sp:
        out = jax.tree_util.tree_map(np.asarray, make())
        nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(out))
        sp.set(nbytes=nbytes)
    parent.set(nbytes=nbytes)
    return out


class ServingEngine:
    def __init__(
        self,
        cfg: ArchConfig,
        params: Any,
        *,
        engine_cfg: Optional[EngineConfig] = None,
        planner: Optional[ReusePlanner] = None,
        backends: Optional[Dict[str, StorageBackend]] = None,
        pricing: Optional[Pricing] = None,
        perf: Optional[PerfModel] = None,
        clock: Optional[SimClock] = None,
        transfer: Optional[TransferModel] = None,
        on_token=None,
        telemetry=None,
        telemetry_replica: int = 0,
        market=None,
        device: Optional[jax.Device] = None,
    ):
        self.cfg = cfg
        # The device this engine's params and KV state are committed to (a
        # ServingCluster gives each replica its own); None = JAX's default.
        # Host-built inputs follow the committed operands onto it.
        self.device = device
        self.params = params if device is None else jax.device_put(params, device)
        self.ec = engine_cfg or EngineConfig()
        self.pricing = pricing or tpu_v5e_pod(8)
        self.perf = perf or PerfModel(tpu_v5e(8, hosts=1))
        self.api = registry.get_model(cfg)
        if self.ec.cost_arch is not None:
            from repro.configs import get_config

            self.cost_cfg = get_config(self.ec.cost_arch)
        else:
            self.cost_cfg = cfg

        # clock/transfer are injectable so a ServingCluster can give every
        # replica its own simulated timeline and per-replica fee accounting
        # while tying shared backends to the right owner (serving/cluster.py)
        self.clock = clock or SimClock()
        self.transfer = transfer or TransferModel(self.perf, self.pricing)
        # streaming per-token hook (off by default): called with every
        # TokenEmitted event, in emission order — first tokens at admission
        # and each decode step's batch in slot order.
        self.on_token = on_token
        # Unified telemetry (obs.Telemetry), off by default.  Entirely
        # host-side: it observes the already-materialized event stream and
        # the transfer model's fee charges, so enabling it cannot change
        # tokens or trigger recompiles.  ``telemetry_replica`` tags this
        # engine's events/ledger entries when it serves inside a cluster.
        self.telemetry = telemetry
        self._replica = telemetry_replica
        if telemetry is not None:
            self.transfer.bind_ledger(telemetry.ledger, replica=telemetry_replica)
        self._c_gpu_s = self.pricing.compute.cost_per_hour / 3600.0
        if self.ec.tier_specs is not None:
            specs = list(self.ec.tier_specs)
        else:
            specs = [TierSpec(n, gb) for n, gb in self.ec.tier_capacities_gb.items()]
        self.backends = backends or build_backends(
            specs, transfer=self.transfer, clock=self.clock, hedge=self.ec.hedge,
            faults=self.ec.faults,
        )
        self.retry_policy = self.ec.retry_policy or RetryPolicy()
        migration = self.ec.migration_policy
        if migration is None and self.ec.migration_interval_s > 0:
            migration = BreakEvenMigrator(compute_cost_per_s=self._c_gpu_s)
        self.store = TieredStore(
            tiers=specs,
            transfer=self.transfer,
            clock=self.clock,
            chunk_tokens=self.ec.chunk_tokens,
            compress_tier=self.ec.compress_tier,
            eviction=self.ec.eviction,
            backends=self.backends,
            pricing=self.pricing,
            migration=migration,
            spill_on_pressure=self.ec.spill_on_pressure,
        )
        self.planner: ReusePlanner = planner or CostAwarePlanner()
        self.planner.configure(
            cost_cfg=self.cost_cfg,
            pricing=self.pricing,
            perf=self.perf,
            write_back=self.ec.reuse_enabled and self.ec.store_write_back,
            min_store_tokens=max(self.ec.chunk_tokens, self.ec.min_cache_tokens),
        )
        # Marketplace session (repro.market.MarketSession), duck-typed so the
        # engine never imports the market package.  Binding publishes this
        # engine's store as the tenant's catalog and hands the market the
        # bit-exactness oracle (market_spot_check).  None = no market; every
        # plan and token is exactly what it was before.
        self.market = market
        if market is not None:
            market.bind_engine(self)
            # a MarketPlanner built without an explicit session inherits
            # this engine's (duck-typed: only planners that can buy have one)
            if getattr(self.planner, "session", "no") is None:
                self.planner.session = market
        self.queue = AdmissionQueue()
        self.slots = [Slot(i) for i in range(self.ec.max_slots)]
        self.records: List[RequestRecord] = []
        # req_id -> clock time its context prefetch completes
        self._prefetch_ready: Dict[int, float] = {}
        # req_id -> entry pinned on its behalf (prefetch/eviction race guard)
        self._prefetch_pins: Dict[int, str] = {}
        # req_id -> (PrefixMatch, entry_id, trie_version): the prefetch pass's
        # trie walk, carried forward to admission so the same context is not
        # walked twice; invalidated by any trie mutation (version bump).
        self._prefetch_lookup: Dict[int, tuple] = {}
        self._next_migration_s = self.ec.migration_interval_s

        self._jit_prefill = jax.jit(self._prefill_impl)
        # the dense state is donated: the engine replaces ``self._state`` with
        # the step's result, so the decode program updates the cache in place
        self._jit_decode = jax.jit(self._decode_impl, donate_argnums=(2,))
        self._jit_packed = (
            jax.jit(self._packed_prefill_impl)
            if self.api.prefill_packed is not None
            else None
        )
        self._packable = (
            self.api.prefill_packed is not None
            and paged.packable_arch(cfg, self.ec.max_len)
        )
        # bytes of one KV row over every layer, K and V: the byte counters
        # of a packed assembly's host span, from the config alone
        self._kv_row_bytes = (
            2 * cfg.n_layers * cfg.n_kv_heads * cfg.resolved_head_dim
            * np.dtype(model_common.resolve_dtype(cfg.dtype)).itemsize
            if self._packable else 0
        )
        # Paged batched decode over the shared KV block pool (packable archs
        # only — the paged layout needs per-position attention state and the
        # block-aligned packed-prefill spans to land admissions in place).
        self._paged_on = (
            self.ec.paged_decode
            and self._packable
            and self.api.decode_paged is not None
        )
        self._paged: Optional[paged.PagedSlots] = None
        if self._paged_on:
            assert self.ec.kv_block == self.ec.pack_align, (
                "packed spans must land block-aligned in the pool",
                self.ec.kv_block, self.ec.pack_align,
            )
            assert self.ec.max_len % self.ec.kv_block == 0, (
                self.ec.max_len, self.ec.kv_block)
            self._paged = paged.PagedSlots(
                self.ec.max_slots, self.ec.max_len, self.ec.kv_block
            )
            self._pool_caches = self._on_device(
                lambda: paged.init_pool_caches(
                    cfg, self._paged.pool.n_blocks, self.ec.kv_block
                )
            )
            self._jit_decode_paged = jax.jit(self._decode_paged_impl)
            # the paged path never touches the dense slotted cache: the pool
            # IS the device KV state (no doubled HBM footprint)
            self._state = None
        else:
            self._state = self._on_device(
                lambda: self.api.init_state(cfg, self.ec.max_slots, self.ec.max_len)
            )
        # Fused non-prefix reuse (CacheBlend-style): chunk-composite lookups
        # + the selective-recompute launch.  Needs the packed path's arch
        # predicate (assembled KV is per-position attention state) and the
        # fused model entry point.
        self._jit_fused = (
            jax.jit(self._fused_prefill_impl)
            if self.api.prefill_fused is not None
            else None
        )
        self._fusion_on = (
            self.ec.fusion_enabled
            and self.ec.reuse_enabled
            and self._packable
            and self._jit_fused is not None
        )
        self.fused_jit = JitBucketStats()
        # Unified continuous-batching step: chunked prefill interleaved with
        # decode in one static-shape launch over the block pool.
        self._jit_chunked = (
            jax.jit(self._chunked_prefill_impl)
            if self.api.prefill_chunked is not None
            else None
        )
        self._unified_on = (
            self.ec.unified_step
            and self._paged_on
            and self._jit_chunked is not None
        )
        # slot index -> in-flight prefill stream (unified mode only)
        self._chunks: Dict[int, _ChunkStream] = {}
        # context-token tuples an unfinished chunk stream will write back:
        # the unified analogue of the packed batch's write-back dedup
        self._wb_inflight: Dict[tuple, int] = {}
        self.unified_jit = JitBucketStats()
        self.unified_steps = 0  # mixed (chunk-carrying) launches
        self.unified_chunk_tokens = 0  # prefill tokens landed via chunks
        self.unified_busy_s = 0.0  # modeled time in mixed launches
        self.fused_admissions = 0
        self.fused_reused_tokens = 0
        self.fused_recompute_tokens = 0
        self.fused_sources = 0
        self.fused_busy_s = 0.0
        # packed-admission observability (benchmarks assert on these)
        self.jit_stats = JitBucketStats()
        self.batches = 0
        self.packed_q_tokens = 0  # useful tokens through the packed kernel
        self.packed_q_len = 0  # padded (bucketed) tokens launched
        self.lookup_walks = 0  # real trie walks
        self.lookup_reuses = 0  # admissions served from the prefetch walk
        self.admission_busy_s = 0.0  # modeled time spent in load+prefill
        self.decode_busy_s = 0.0  # modeled time spent in decode steps
        self.decode_tokens = 0  # tokens emitted by decode steps
        # failure handling observability (fault injection / retry / degrade)
        self.fetch_failures = 0  # failed fetch attempts (every attempt)
        self.fetch_retries = 0  # attempts the retry policy re-issued
        self.degraded_requests = 0  # admissions that fell back to recompute
        self.fetch_wasted_s = 0.0  # time burned by failed attempts + backoff
        self.fetch_wasted_bytes = 0.0  # transfer bytes charged but unusable
        # marketplace observability (None market = all stay 0)
        self.market_purchases = 0  # plans served with bought peer KV
        self.market_failed = 0  # purchases that degraded to recompute
        self.market_spend = 0.0  # buyer dollars settled through the market

    def _on_device(self, make):
        """Build device state with ``make()`` on this engine's device,
        committed there (as is when no device was given)."""
        if self.device is None:
            return make()
        with jax.default_device(self.device):
            return jax.device_put(make(), self.device)

    # ------------------------------------------------------------------ #
    # jit'd compute
    # ------------------------------------------------------------------ #
    def _prefill_impl(self, params, tokens, state, embeds=None):
        return self.api.prefill(params, self.cfg, tokens, state, embeds=embeds)

    def _packed_prefill_impl(
        self, params, tokens, caches, q_pos, q_seg, q_rows, kv_pos, kv_seg, last_idx
    ):
        return self.api.prefill_packed(
            params, self.cfg, tokens, caches,
            q_pos=q_pos, q_seg=q_seg, q_rows=q_rows,
            kv_pos=kv_pos, kv_seg=kv_seg, last_idx=last_idx,
        )

    def _fused_prefill_impl(self, params, tokens, caches, q_pos, q_rows, kv_pos, last_idx):
        return self.api.prefill_fused(
            params, self.cfg, tokens, caches,
            q_pos=q_pos, q_rows=q_rows, kv_pos=kv_pos, last_idx=last_idx,
        )

    def _decode_impl(self, params, tokens, state, active):
        logits, new_state = self.api.decode(params, self.cfg, tokens, state)
        # inactive slots: freeze position (their cache row writes are masked
        # by pos-based validity on the next real request).
        pos = jnp.where(active, new_state.pos, state.pos)
        new_state = new_state._replace(pos=pos)
        return logits, new_state

    def _decode_paged_impl(self, params, tokens, caches, tables, pos):
        # positions/tables are host-managed (PagedSlots); freed slots carry
        # zeroed tables, routing their stale writes onto the dump block.
        return self.api.decode_paged(
            params, self.cfg, tokens, caches,
            block_table=tables, pos=pos, block=self.ec.kv_block,
        )

    def _chunked_prefill_impl(self, params, tokens, caches, tables, q_pos, last_idx):
        # the unified step's mixed launch: every row is a [C]-token window —
        # a prefill chunk, a decode token at index 0, or all padding.  All
        # shapes are static ([B, C] tokens, [B, nb] tables), so steady
        # unified serving compiles exactly once.
        return self.api.prefill_chunked(
            params, self.cfg, tokens, caches,
            block_table=tables, q_pos=q_pos, last_idx=last_idx,
            block=self.ec.kv_block,
        )

    # ------------------------------------------------------------------ #
    # Public API: submit / step / drain / run
    # ------------------------------------------------------------------ #
    def submit(self, req: Request) -> None:
        self.queue.push(req)

    @property
    def idle(self) -> bool:
        """Nothing queued, nothing decoding, no prefill chunks in flight."""
        return (
            len(self.queue) == 0
            and not any(s.active for s in self.slots)
            and not self._chunks
        )

    def load(self) -> int:
        """Requests this replica currently owes work to (queued + in a slot,
        including slots mid-chunked-prefill) — the router's load signal."""
        return (
            len(self.queue)
            + sum(1 for s in self.slots if s.active)
            + len(self._chunks)
        )

    def free_capacity(self) -> int:
        """Slots not yet spoken for by queued or active requests (floor 0)."""
        return max(0, self.ec.max_slots - self.load())

    def step(self) -> List[ev.Event]:
        """Advance the engine by one scheduling step and return its events:
        admit every admissible request with a free slot as one packed batch
        (one ragged suffix-prefill launch), else run one batched decode step,
        else jump the clock to the next arrival.  A due migration pass
        (EngineConfig.migration_interval_s) piggybacks on the step and
        surfaces as TierMigrated events."""
        with jax.default_device(self.device), span("engine.step"):
            events = self._step()
        if self.telemetry is not None and events:
            self.telemetry.on_events(events, replica=self._replica)
        return events

    def _step(self) -> List[ev.Event]:
        if self._unified_on:
            return self._step_unified()
        events: List[ev.Event] = []
        self._run_migrations(events)
        if self._admit_batch(events):
            return events
        if any(s.active for s in self.slots):
            self._decode_step(events)
            return events
        nxt = self.queue.next_arrival()
        if nxt is None:
            return events  # fully drained
        self._advance_clock(nxt, events)
        return events

    def _advance_clock(self, to_s: float, events: List[ev.Event]) -> None:
        """Jump the idle clock to ``to_s``, stepping through every migration
        pass whose scheduled time falls inside the gap.  Each missed pass
        runs AT its own due time (the clock walks to each crossing before
        the final jump), so a diurnal idle gap accrues storage dollars and
        demotes cold entries on schedule — instead of collapsing all missed
        passes into one late one at the far edge of the gap."""
        if self.ec.migration_interval_s > 0 and self.store.migration is not None:
            while self._next_migration_s <= to_s:
                at = self._next_migration_s
                self.clock.at_least(at)
                self.store.run_migrations()
                self._next_migration_s = at + self.ec.migration_interval_s
                self._emit_migrations(events)
        self.clock.at_least(to_s)
        events.append(ev.ClockAdvanced(t_s=self.clock.now, req_id=-1, to_s=to_s))

    def drain(self) -> Iterator[ev.Event]:
        """Iterate events until every submitted request has finished."""
        while not self.idle:
            yield from self.step()

    def run(self) -> metrics_mod.ServingSummary:
        """Serve everything submitted; returns the summary."""
        for _ in self.drain():
            pass
        return self.summary()

    def summary(self) -> metrics_mod.ServingSummary:
        if self.telemetry is not None:
            # settle accrued GB-hours into the ledger at the same instant the
            # summary reads them, so the conservation check is exact
            self.telemetry.settle_engine(self, replica=self._replica)
        return metrics_mod.summarize(
            self.records,
            storage_cost=self.store.storage_cost(self.pricing),
            transfer_cost=self.transfer.transfer_fees(),
        )

    def _attr(self, activity: str, req_id: Optional[int] = None):
        """Attribution scope for transfer fees charged inside; a nullcontext
        when telemetry is off (the common case pays one ``is None``)."""
        if self.telemetry is None:
            return contextlib.nullcontext()
        return self.transfer.attributed(activity=activity, req_id=req_id)

    # ------------------------------------------------------------------ #
    # Tier migration (clock-driven economics pass)
    # ------------------------------------------------------------------ #
    def _run_migrations(self, events: List[ev.Event]) -> None:
        if (
            self.ec.migration_interval_s <= 0
            or self.store.migration is None
            or self.clock.now < self._next_migration_s
        ):
            return
        self.store.run_migrations()
        self._next_migration_s = self.clock.now + self.ec.migration_interval_s
        self._emit_migrations(events)

    def _emit_migrations(self, events: List[ev.Event]) -> None:
        """Surface store migrations (policy passes AND pressure spills) as
        typed events, stamped with the move's own SimClock time."""
        for m in self.store.drain_migrations():
            events.append(
                ev.TierMigrated(
                    t_s=m.t_s, req_id=-1, entry_id=m.entry_id,
                    from_tier=m.from_tier, to_tier=m.to_tier,
                    nbytes=m.nbytes, reason=m.reason,
                )
            )

    # ------------------------------------------------------------------ #
    # Admission: pop -> plan (per request) -> execute (one packed batch)
    # ------------------------------------------------------------------ #
    def _free_slots(self) -> List[Slot]:
        return [s for s in self.slots if not s.active]

    def _admit_batch(self, events: List[ev.Event]) -> bool:
        """Admit every admissible request with a free slot (up to
        ``admit_batch``): plan each individually, then execute all packable
        suffix-prefills as ONE packed ragged kernel launch.  Requests the
        packed path cannot carry (SSM/hybrid/enc-dec state, embeds, ring
        caches) fall back to the per-request path, one per step."""
        free = self._free_slots()
        if not free:
            return False
        limit = min(len(free), self.ec.admit_batch or self.ec.max_slots)
        reqs: List[Request] = []
        while len(reqs) < limit:
            nxt = self.queue.peek_next(self.clock.now)
            if nxt is None:
                break
            if not (self._packable and nxt.embeds is None):
                if reqs:
                    break  # pack what we have; the odd one waits a step
                req = self.queue.pop_admissible(self.clock.now)
                return self._admit_single(req, free[0], events)
            reqs.append(self.queue.pop_admissible(self.clock.now))
        if not reqs:
            return False

        with span("engine.admit", n=len(reqs), req_ids=_ids(reqs)):
            # Plan sequentially, carrying each planned fetch's bytes forward so
            # batch-mate i's predicted queue wait sees mates 0..i-1 on the same
            # contended link — at execute time their reservations land in this
            # order at one shared instant, and the planner must price that.
            pending: Dict[str, List[float]] = {}
            admissions: List[_Admission] = []
            for req, slot in zip(reqs, free):
                a = self._plan_admission(req, slot, events, pending=pending)
                admissions.append(a)
                if a.plan.action == "fused":
                    # pin every fusion source now: a batch-mate's write-back
                    # could otherwise evict it before the fused fetch executes
                    for eid in a.plan.fused.source_entries:
                        if eid in self.store.entries:
                            self.store.pin(eid)
                            a.pins.append(eid)
                    # the fused fetches hit their tiers' links at the shared
                    # admission instant too: later batch-mates must price them
                    for tier, b in a.lookup.fused_bytes_by_tier.items():
                        pending.setdefault(tier, []).append(b)
                if a.plan.loads_kv and a.lookup.entry is not None:
                    pending.setdefault(a.lookup.entry.tier, []).append(
                        self._entry_fetch_bytes(a.lookup.entry, a.plan.matched_tokens)
                    )
            packed = [a for a in admissions if a.plan.action != "fused"]
            if packed:
                self._execute_packed(packed, events)
            for a in admissions:
                if a.plan.action == "fused":
                    self._execute_fused(a, events)
            self._issue_prefetches()
            return True

    def _plan_admission(
        self,
        req: Request,
        slot: Slot,
        events: List[ev.Event],
        pending: Optional[Dict[str, List[float]]] = None,
    ):
        with span("engine.plan", req=req.req_id):
            rec = RequestRecord(
                req_id=req.req_id,
                arrival_s=req.arrival_s,
                context_len=len(req.context_tokens),
                prompt_len=len(req.prompt_tokens),
                start_s=self.clock.now,
            )
            total_len = len(req.context_tokens) + len(req.prompt_tokens) + req.max_new_tokens
            assert total_len <= self.ec.max_len, (total_len, self.ec.max_len)
            events.append(
                ev.RequestAdmitted(
                    t_s=self.clock.now, req_id=req.req_id, slot=slot.index,
                    queue_s=rec.queue_s,
                )
            )
            with span("store.lookup", req=req.req_id) as sp:
                lookup = self._lookup(req, pending)
                sp.set(matched_tokens=lookup.match.matched_tokens if lookup.match else 0)
            workload = Workload(
                L_context=len(req.context_tokens),
                L_prompt=len(req.prompt_tokens),
                L_output=req.max_new_tokens,
                N=max(int(req.expected_reuses), 1),
                slo_ttft_s=req.slo_ttft_s,
            )
            plan = self.planner.plan(req, lookup, workload)
            events.append(ev.PlanChosen(t_s=self.clock.now, req_id=req.req_id, plan=plan))
            return _Admission(req=req, rec=rec, slot=slot, plan=plan, lookup=lookup)

    def _finish_admission(
        self, a: "_Admission", first_tok: int, events: List[ev.Event]
    ) -> None:
        """Shared admission epilogue (post clock-advance): record fields that
        are common to both execute paths, emit the first token, activate."""
        a.rec.action = (
            a.plan.action if (a.plan.reuses_kv and not a.rec.degraded)
            else "recompute"
        )
        a.rec.plan = a.plan
        a.rec.tokens.append(first_tok)
        tok_ev = ev.TokenEmitted(
            t_s=self.clock.now, req_id=a.req.req_id, token=first_tok, index=0
        )
        events.append(tok_ev)
        if self.on_token is not None:
            self.on_token(tok_ev)
        a.slot.request = a.req
        a.slot.record = a.rec
        a.slot.generated = 1
        a.slot.last_token = first_tok
        a.slot.active = True
        self._maybe_finish(a.slot, events)

    # -- per-request (fallback) execution ------------------------------- #
    def _admit_single(self, req: Request, slot: Slot, events: List[ev.Event]) -> bool:
        with span("engine.admit", n=1, req_ids=str(req.req_id)):
            a = self._plan_admission(req, slot, events)
            if a.plan.market is not None:
                self._market_fetch(a, events)
            elif a.plan.loads_kv and a.lookup.entry is not None:
                self._fetch_kv_resilient(a, events)
            if a.artifact is not None:
                load_s, prefill_s, logits, temp = self._execute_load(req, a, events)
                matched = a.matched
            else:
                # plain recompute, or a degraded fetch falling back to exact
                # recompute mid-admission — the burned fetch time rides on load_s
                # (a.delay is 0.0 on the plain path)
                load_s, matched = a.delay, 0
                prefill_s, logits, temp = self._execute_recompute(req, a.plan, events)
            self._release_prefetch(req.req_id)

            # ---- install into the batch slot ------------------------------- #
            self._land_state(slot, temp, req)
            first_tok = _first_token(logits[0])

            self.clock.advance(load_s + prefill_s)
            self.admission_busy_s += load_s + prefill_s
            a.rec.matched_tokens = matched
            a.rec.load_s = load_s
            a.rec.prefill_s = prefill_s
            a.rec.compute_cost += self._c_gpu_s * prefill_s
            self._finish_admission(a, first_tok, events)
            self._issue_prefetches()
            return True

    # -- packed batch execution ----------------------------------------- #
    def _execute_packed(
        self, admissions: List["_Admission"], events: List[ev.Event]
    ) -> None:
        """Execute a whole admission batch as one packed ragged suffix-prefill:
        per-request storage fetches (queueing on contended links is modeled at
        the shared admission instant), one kernel launch over the concatenated
        token runs, outputs scattered back into each request's batch slot."""
        t0 = self.clock.now
        for a in admissions:
            if a.plan.market is not None:
                self._market_fetch(a, events)
            elif a.plan.loads_kv and a.lookup.entry is not None:
                self._fetch_kv_resilient(a, events)
            self._release_prefetch(a.req.req_id)
            ctx = list(a.req.context_tokens)
            a.new_tokens = ctx[a.matched:] + list(a.req.prompt_tokens)

        layout = paged.pack_layout(
            [a.slot.index for a in admissions],
            [a.matched for a in admissions],
            [len(a.new_tokens) for a in admissions],
            align=self.ec.pack_align,
            bucket_min=self.ec.pack_bucket_min,
        )
        jit_hit = self.jit_stats.record((layout.q_len, layout.kv_len))
        self.batches += 1
        self.packed_q_tokens += layout.q_tokens
        self.packed_q_len += layout.q_len
        events.append(
            ev.BatchAdmitted(
                t_s=t0, req_id=-1,
                req_ids=tuple(a.req.req_id for a in admissions),
                q_tokens=layout.q_tokens, q_len=layout.q_len,
                kv_len=layout.kv_len, jit_hit=jit_hit,
            )
        )

        logits, new_caches = self._packed_launch(
            layout,
            [a.new_tokens for a in admissions],
            [a.artifact for a in admissions],
            jit_hit=jit_hit,
        )

        lens = [len(a.new_tokens) for a in admissions]
        prefill_s = self.perf.t_prefill_packed(self.cost_cfg, lens)
        total_new = sum(lens)
        written = set()  # contexts written back within THIS batch (dedup:
        # several batch-mates recomputing the same context store it once)
        for a, seg in zip(admissions, layout.segments):
            if a.artifact is not None:
                a.load_s = (
                    max(0.0, a.delay - prefill_s) if self.ec.overlap_load else a.delay
                )
                # KVLoaded carries THIS request's own fetch remainder; the
                # batch-barrier wait it actually experiences lands on the
                # record below.
                events.append(
                    ev.KVLoaded(
                        t_s=t0, req_id=a.req.req_id,
                        tier=(
                            a.lookup.entry.tier
                            if a.lookup.entry is not None
                            else (a.plan.tier or "market")
                        ),
                        nbytes=a.nbytes, load_s=a.load_s,
                        matched_tokens=a.matched,
                    )
                )
            else:
                if a.rec.degraded:
                    # the burned fetch time still delays this request (and,
                    # through the batch barrier below, its batch-mates)
                    a.load_s = a.delay
                if a.plan.store_after and tuple(a.req.context_tokens) not in written:
                    written.add(tuple(a.req.context_tokens))
                    ctx_len = len(a.req.context_tokens)
                    with span("engine.write_back", req=a.req.req_id) as sp:
                        art = _to_host(lambda: paged.packed_to_artifact(
                            self.cfg, new_caches, seg, ctx_len), sp)
                        self._write_back(a.req, art, events)
            events.append(
                ev.PrefillDone(
                    t_s=t0, req_id=a.req.req_id,
                    n_tokens=len(a.new_tokens), prefill_s=prefill_s,
                )
            )

        batch_load = max((a.load_s for a in admissions), default=0.0)
        self.clock.advance(batch_load + prefill_s)
        self.admission_busy_s += batch_load + prefill_s

        if self._paged_on:
            # packed outputs land DIRECTLY in the shared block pool: one
            # scatter for the whole batch, no per-slot re-materialization.
            with span("engine.land", req_ids=_ids(a.req for a in admissions),
                      n_tokens=sum(seg.n_total for seg in layout.segments)):
                self._land_packed_in_pool(admissions, layout, new_caches)
        for i, (a, seg) in enumerate(zip(admissions, layout.segments)):
            if not self._paged_on:
                with span("engine.land", req=a.req.req_id, n_tokens=seg.n_total):
                    self._state = paged.insert_slot(
                        self.cfg, self._state, seg.slot,
                        paged.packed_to_artifact(self.cfg, new_caches, seg, seg.n_total),
                    )
            a.rec.matched_tokens = a.matched
            # every batch member waits the load BARRIER (max of the batch's
            # fetches) before the shared kernel: record the realized wait so
            # ttft_s agrees with the TokenEmitted timeline and the SLO audit
            a.rec.load_s = batch_load
            a.rec.prefill_s = prefill_s
            a.rec.compute_cost += (
                self._c_gpu_s * prefill_s * (len(a.new_tokens) / total_new)
            )
            self._finish_admission(a, _first_token(logits[i]), events)

    def _packed_launch(self, layout: paged.PackLayout, new_tokens, artifacts,
                       jit_hit: Optional[bool] = None):
        """One packed ragged suffix-prefill launch: every segment's reused
        prefix rows preloaded from its artifact (None = recompute), its new
        tokens prefilled.  Returns (last-token logits per segment, packed
        caches).  ``jit_hit`` (the bucket's hit, where the caller recorded
        it) only labels the launch's host span."""
        row_bytes = self._kv_row_bytes
        stored = sum(s.matched for s, art in zip(layout.segments, artifacts)
                     if art is not None and s.matched > 0)
        with span("engine.assemble", q_len=layout.q_len, kv_len=layout.kv_len,
                  bucket_bytes=layout.kv_len * row_bytes,
                  stored_bytes=stored * row_bytes):
            arrays = paged.pack_arrays(layout, new_tokens)
            caches = paged.build_packed_caches(self.cfg, layout, artifacts)
            last_idx = np.zeros((self.ec.max_slots,), np.int32)
            for i, seg in enumerate(layout.segments):
                last_idx[i] = seg.q_last
            index = [arrays[k] for k in ("tokens", "q_pos", "q_seg", "q_rows",
                                         "kv_pos", "kv_seg")] + [last_idx]
            with span("engine.h2d", nbytes=sum(x.nbytes for x in index)):
                tokens, q_pos, q_seg, q_rows, kv_pos, kv_seg, last = map(
                    jnp.asarray, index)
        hit = {} if jit_hit is None else {"jit_hit": int(jit_hit)}
        with span("engine.launch", program="packed_prefill", q_len=layout.q_len,
                  kv_len=layout.kv_len, **hit):
            return self._jit_packed(
                self.params, tokens, caches, q_pos, q_seg, q_rows, kv_pos, kv_seg, last,
            )

    def prefill_logits(self, context_tokens, prompt_tokens, artifact=None) -> jax.Array:
        """Logits [vocab] of the first output token after context + prompt,
        computed by the packed admission launch (the default admission
        path): the stored ``artifact``'s KV stands in for the whole context
        and only the prompt is prefilled; ``None`` prefills everything.
        Pure compute — no clock, store, slot or bill changes — so a caller
        can check a reused request's logits against full recompute."""
        if not self._packable:
            raise ValueError(f"{self.cfg.name}: no packed admission path")
        ctx, prompt = list(context_tokens), list(prompt_tokens)
        matched = len(ctx) if artifact is not None else 0
        new = ctx[matched:] + prompt
        layout = paged.pack_layout(
            [0], [matched], [len(new)],
            align=self.ec.pack_align, bucket_min=self.ec.pack_bucket_min,
        )
        with jax.default_device(self.device):
            logits, _ = self._packed_launch(layout, [new], [artifact])
        return logits[0]

    # -- fused (chunk-composite) execution ------------------------------ #
    def _execute_fused(self, a: "_Admission", events: List[ev.Event]) -> None:
        """Execute a ``"fused"`` plan: fetch each source entry's matched
        rows (fetches issue concurrently — the request waits the slowest),
        assemble one query-ordered KV buffer with the reused spans preloaded
        (K delta-RoPE'd to its target position), run ONE selective-recompute
        launch over just the recompute spans + prompt, and land the full
        context+prompt state in the slot (block pool or dense).  At
        ``recompute_frac=1.0`` this is bit-identical to a full recompute
        admission (tests/test_fusion.py)."""
        t0 = self.clock.now
        req, schedule = a.req, a.plan.fused
        ctx, prompt = list(req.context_tokens), list(req.prompt_tokens)

        out = self._fetch_fused_sources(a, events)
        if out is None:
            # one lost source spoils the composite: the whole fused
            # admission degrades to exact recompute (time already burned
            # on earlier sources rides along, on a.delay)
            self._degrade_fused(a, events)
            return
        sources, fetched = out
        delays = [d for _, _, d, _ in fetched]

        layout = fusion.fused_layout(
            schedule, len(prompt),
            align=self.ec.pack_align, bucket_min=self.ec.pack_bucket_min,
        )
        row_bytes = self._kv_row_bytes
        with span("engine.assemble", q_len=layout.q_len, kv_len=layout.kv_len,
                  bucket_bytes=layout.kv_len * row_bytes,
                  stored_bytes=schedule.reused_tokens * row_bytes):
            caches = fusion.build_fused_caches(
                self.cfg, schedule, sources, layout.kv_len
            )
            arrays = fusion.fused_arrays(schedule, ctx, prompt, layout)
            index = [arrays[k] for k in ("tokens", "q_pos", "q_rows", "kv_pos", "last_idx")]
            with span("engine.h2d", nbytes=sum(x.nbytes for x in index)):
                tokens, q_pos, q_rows, kv_pos, last = map(jnp.asarray, index)
        jit_hit = self.fused_jit.record((layout.q_len, layout.kv_len))
        with span("engine.launch", program="fused_prefill", q_len=layout.q_len,
                  kv_len=layout.kv_len, jit_hit=int(jit_hit)):
            logits, new_caches = self._jit_fused(
                self.params, tokens, caches, q_pos, q_rows, kv_pos, last,
            )

        prefill_s = self.perf.t_prefill_fused(
            self.cost_cfg, layout.total, layout.n_q
        )
        load_s = max(delays, default=0.0)
        if self.ec.overlap_load:
            load_s = max(0.0, load_s - prefill_s)
        for tier, nbytes, delay, rows in fetched:
            # like the prefix-load path, each KVLoaded carries the delay
            # charged post-overlap, not the raw link time
            events.append(
                ev.KVLoaded(
                    t_s=t0, req_id=req.req_id, tier=tier, nbytes=nbytes,
                    load_s=(
                        max(0.0, delay - prefill_s)
                        if self.ec.overlap_load else delay
                    ),
                    matched_tokens=rows,
                )
            )
        events.append(
            ev.FusedAdmitted(
                t_s=t0, req_id=req.req_id, slot=a.slot.index,
                reused_tokens=schedule.reused_tokens,
                recompute_tokens=schedule.recompute_tokens,
                n_spans=len(schedule.spans), n_sources=len(sources),
                q_len=layout.q_len, kv_len=layout.kv_len, jit_hit=jit_hit,
            )
        )
        events.append(
            ev.PrefillDone(
                t_s=t0, req_id=req.req_id,
                n_tokens=layout.n_q, prefill_s=prefill_s,
            )
        )

        # land the assembled+recomputed state: rows [0, total) ARE the
        # context+prompt state in sequence order.  The artifact carries
        # whole-kv_block row coverage (the pool landing copies whole blocks)
        # while pos stays the true token count.
        seg = paged.PackSegment(
            slot=a.slot.index, kv_start=0, q_start=0,
            matched=schedule.reused_tokens, n_new=layout.n_q,
            n_total=layout.total,
        )
        n_rows = -(-layout.total // self.ec.kv_block) * self.ec.kv_block
        art = paged.packed_to_artifact(
            self.cfg, new_caches, seg, min(n_rows, layout.kv_len)
        )._replace(pos=jnp.full((1,), layout.total, jnp.int32))
        self._land_state(a.slot, art, req)

        self.clock.advance(load_s + prefill_s)
        self.admission_busy_s += load_s + prefill_s
        self.fused_busy_s += load_s + prefill_s
        self.fused_admissions += 1
        self.fused_reused_tokens += schedule.reused_tokens
        self.fused_recompute_tokens += schedule.recompute_tokens
        self.fused_sources += len(sources)
        a.rec.matched_tokens = schedule.reused_tokens
        a.rec.load_s = load_s
        a.rec.prefill_s = prefill_s
        a.rec.compute_cost += self._c_gpu_s * prefill_s
        self._finish_admission(a, _first_token(logits[0]), events)

    def _fetch_fused_sources(self, a: "_Admission", events: List[ev.Event]):
        """Fetch every fused source entry's matched rows (pinned at plan
        time) under the retry policy.  On success returns ``(sources,
        fetched)`` — ``sources[entry_id]`` the artifact, ``fetched`` one
        (tier, nbytes, delay_s, rows) tuple per source — with pins and the
        prefetch released.  On exhaustion of any source, degrades the
        admission in place (record marked, DegradedToRecompute emitted, the
        burned time left on ``a.delay``) and returns None: the caller falls
        back to exact recompute, so tokens match the fault-free run."""
        req, schedule = a.req, a.plan.fused
        sources: Dict[str, Any] = {}
        fetched: List[tuple] = []  # (tier, nbytes, delay, rows) per source
        wasted_total = 0.0
        for eid, rows in schedule.rows_by_entry().items():
            e = self.store.entries[eid]  # pinned at plan time: must exist
            nbytes = self._entry_fetch_bytes(e, rows)
            override = nbytes if self.cost_cfg is not self.cfg else None

            def attempt(activity, eid=eid, e=e, rows=rows, override=override,
                        nbytes=nbytes):
                with self._attr(activity, req.req_id), span(
                    "store.fetch", req=req.req_id, tier=e.tier, nbytes=int(nbytes)
                ):
                    return self.store.fetch(
                        eid, fraction=rows / max(e.n_tokens, 1), nbytes=override
                    )

            out, wasted, attempts = self._retry_fetch(
                req, tier=e.tier, entry_id=eid, matched=rows, nbytes=nbytes,
                attempt_fn=attempt, events=events,
            )
            wasted_total += wasted
            if out is None:
                for pid in a.pins:
                    self.store.unpin(pid)
                a.pins.clear()
                self._release_prefetch(req.req_id)
                self.degraded_requests += 1
                a.rec.degraded = True
                a.delay = wasted_total
                events.append(ev.DegradedToRecompute(
                    t_s=self.clock.now, req_id=req.req_id, tier=e.tier,
                    entry_id=eid, attempts=attempts, wasted_s=wasted_total,
                    reason="fused_source_failed",
                ))
                return None
            art, delay = out
            sources[eid] = art
            fetched.append((e.tier, nbytes, wasted + delay, rows))
        for eid in a.pins:
            self.store.unpin(eid)
        a.pins.clear()
        self._release_prefetch(req.req_id)
        return sources, fetched

    def _degrade_fused(self, a: "_Admission", events: List[ev.Event]) -> None:
        """A fused source fetch exhausted its retries (record already marked
        by ``_fetch_fused_sources``, burned time on ``a.delay``): run the
        request as one exact full recompute (tokens unchanged — recompute is
        the ground truth the fusion approximates from)."""
        req, wasted_s = a.req, a.delay
        prefill_s, logits, temp = self._execute_recompute(req, a.plan, events)
        self._land_state(a.slot, temp, req)
        self.clock.advance(wasted_s + prefill_s)
        self.admission_busy_s += wasted_s + prefill_s
        a.rec.matched_tokens = 0
        a.rec.load_s = wasted_s
        a.rec.prefill_s = prefill_s
        a.rec.compute_cost += self._c_gpu_s * prefill_s
        self._finish_admission(a, _first_token(logits[0]), events)

    # -- shared-block-pool landings (paged decode) ---------------------- #
    def _pool_update(self, dst: np.ndarray, sources) -> None:
        """Land KV rows at pool rows ``dst``: ``sources`` yields one
        (k_rows, v_rows) pair per layer kind, aligned with the pool caches —
        the single scatter shared by every landing path."""
        self._pool_caches = tuple(
            paged.BlockCache(
                paged.KVCache(
                    pc.attn.k.at[:, dst].set(ks), pc.attn.v.at[:, dst].set(vs)
                ),
                None,
            )
            for pc, (ks, vs) in zip(self._pool_caches, sources)
        )

    def _land_packed_in_pool(
        self, admissions: List["_Admission"], layout: paged.PackLayout, new_caches
    ) -> None:
        """Move every segment's kv span from the packed buffers into the
        shared block pool.  Segments are kv_block-aligned (pack_align ==
        kv_block), so a span IS a run of whole blocks: the whole batch lands
        as ONE device scatter per layer kind.  Batch-mates that loaded the
        same stored entry point their table prefixes at one refcounted copy
        of its full blocks (the write-back dedup, carried into the pool);
        only each segment's own blocks are copied."""
        block = self.ec.kv_block
        src_blocks: List[int] = []
        dst_blocks: List[int] = []
        leaders: Dict[str, tuple] = {}  # entry_id -> (slot, matched)
        for a, seg in zip(admissions, layout.segments):
            shared_from, shared = None, 0
            if a.artifact is not None and a.lookup.entry is not None:
                led = leaders.get(a.lookup.entry.entry_id)
                if led is not None:
                    shared_from, led_matched = led
                    # a block is shareable iff BOTH mates' reused prefixes
                    # cover it fully; the boundary block stays private (the
                    # copy-on-write line at the shared-suffix boundary)
                    shared = min(a.matched, led_matched) // block
                else:
                    leaders[a.lookup.entry.entry_id] = (seg.slot, a.matched)
            own = self._paged.admit(
                seg.slot, seg.n_total, shared_from=shared_from,
                shared_blocks=shared,
            )
            first = seg.kv_start // block
            for j, bid in enumerate(own, start=shared):
                src_blocks.append(first + j)
                dst_blocks.append(bid)
        src = paged.block_rows(src_blocks, block)
        dst = paged.block_rows(dst_blocks, block)
        self._pool_update(
            dst, ((nc.attn.k[:, 0, src], nc.attn.v[:, 0, src]) for nc in new_caches)
        )

    def _land_state(self, slot: Slot, temp, req: Request) -> None:
        """Install a freshly prefilled batch-1 state in ``slot``: the block
        pool under paged decode, else the dense batched state."""
        n_tokens = len(req.context_tokens) + len(req.prompt_tokens)
        with span("engine.land", req=req.req_id, n_tokens=n_tokens):
            if self._paged_on:
                self._land_state_in_pool(slot, temp)
            else:
                self._state = paged.insert_slot(self.cfg, self._state, slot.index, temp)

    def _land_state_in_pool(self, slot: Slot, temp) -> None:
        """Per-request fallback admissions (embeds) under paged decode: copy
        the freshly prefilled batch-1 state's rows into newly allocated pool
        blocks (the single-segment analogue of ``_land_packed_in_pool``)."""
        block = self.ec.kv_block
        n_total = int(np.asarray(temp.pos)[0])
        own = self._paged.admit(slot.index, n_total)
        dst = paged.block_rows(own, block)
        n_rows = len(own) * block  # <= max_len (max_len % kv_block == 0)
        self._pool_update(
            dst,
            (
                (tc.attn.k[:, 0, :n_rows], tc.attn.v[:, 0, :n_rows])
                for tc in temp.caches
            ),
        )

    def _copy_pool_blocks(self, splits: List[paged.CowSplit]) -> None:
        """Copy-on-write: duplicate shared boundary blocks onto private ones
        before a decode write touches them (one gather/scatter pair)."""
        block = self.ec.kv_block
        src = paged.block_rows([s.src for s in splits], block)
        dst = paged.block_rows([s.dst for s in splits], block)
        self._pool_update(
            dst,
            ((pc.attn.k[:, src], pc.attn.v[:, src]) for pc in self._pool_caches),
        )

    def _fetch_kv(self, req: Request, plan: ReusePlan, lookup: StoreLookup,
                  activity: str = "fetch"):
        """Charge + execute the storage fetch of a load/partial plan; returns
        (artifact, delay_s, billed_nbytes).  A lookahead prefetch already in
        flight shrinks the delay to its unfinished remainder.  ``activity``
        tags the ledger attribution ("fetch_retry" on re-issued attempts, so
        retry dollars are separable)."""
        entry = lookup.entry
        matched = plan.matched_tokens
        nbytes = plan.fetch_bytes
        override = None
        if self.cost_cfg is not self.cfg:
            # economics-at-scale: charge the FULL arch's KV bytes, and occupy
            # the tier's link for them — queueing under burst (concurrency-
            # limited backends) is modeled at the same scale as the delay.
            nbytes = self._entry_fetch_bytes(entry, matched)
            override = nbytes
        with self._attr(activity, req.req_id), span(
            "store.fetch", req=req.req_id, tier=entry.tier, nbytes=int(nbytes)
        ):
            artifact, delay = self.store.fetch(
                entry.entry_id, fraction=matched / entry.n_tokens, nbytes=override
            )
        ready = self._prefetch_ready.pop(req.req_id, None)
        if ready is not None:
            # fetch was issued while earlier requests were being served:
            # only the unfinished remainder delays this request.
            delay = max(0.0, min(delay, ready - self.clock.now))
        return artifact, delay, nbytes

    # -- failure handling: cost-aware retry + graceful degradation -------- #
    def _retry_fetch(self, req: Request, *, tier: str, entry_id: str,
                     matched: int, nbytes: float, attempt_fn,
                     events: List[ev.Event]):
        """Run one storage fetch (``attempt_fn(activity)``) under the
        cost-aware retry policy.  Returns (result | None, wasted_s, attempts):
        result is whatever ``attempt_fn`` returned on success; None means
        every attempt failed (or retrying stopped beating recompute) and the
        caller must degrade.  ``wasted_s`` accumulates the failed attempts'
        charged delays plus backoff waits; the dollars those attempts burned
        were already charged to the transfer model when their bytes moved."""
        policy = self.retry_policy
        wasted = 0.0
        attempt = 0
        while True:
            attempt += 1
            try:
                out = attempt_fn("fetch" if attempt == 1 else "fetch_retry")
                return out, wasted, attempt
            except StorageError as exc:
                wasted += exc.delay_s
                self.fetch_failures += 1
                self.fetch_wasted_s += exc.delay_s
                self.fetch_wasted_bytes += exc.wasted_bytes
                events.append(ev.FetchFailed(
                    t_s=self.clock.now, req_id=req.req_id, tier=tier,
                    entry_id=entry_id, attempt=attempt, reason=exc.reason,
                    wasted_s=exc.delay_s, wasted_bytes=exc.wasted_bytes,
                ))
                if self.telemetry is not None:
                    # zero-$ marker: the wasted transfer dollars themselves
                    # were charged (stats AND ledger) when the bytes moved,
                    # so conservation already covers them — this entry makes
                    # the waste queryable per request/tier
                    self.telemetry.ledger.add(
                        "transfer", "fetch_failed", 0.0,
                        replica=self._replica, req_id=req.req_id,
                        tier=tier, nbytes=exc.wasted_bytes, kind="load",
                    )
                backoff = policy.backoff(attempt)
                retry_cost = policy.retry_cost(
                    backoff_s=backoff,
                    est_load_s=self.store.estimate_load_delay(tier, nbytes),
                    nbytes=nbytes,
                    gpu_cost_per_s=self._c_gpu_s,
                    per_gb_fee=self.pricing.tier(tier).per_gb_transfer_fee,
                )
                recompute_cost = self._c_gpu_s * self.perf.t_prefill(
                    self.cost_cfg, max(matched, 1)
                )
                if policy.should_retry(exc, attempt, tier=tier,
                                       retry_cost=retry_cost,
                                       recompute_cost=recompute_cost):
                    wasted += backoff
                    self.fetch_wasted_s += backoff
                    self.fetch_retries += 1
                    events.append(ev.FetchRetried(
                        t_s=self.clock.now, req_id=req.req_id, tier=tier,
                        entry_id=entry_id, attempt=attempt + 1,
                        backoff_s=backoff,
                    ))
                    continue
                return None, wasted, attempt

    def _fetch_kv_resilient(self, a: "_Admission", events: List[ev.Event]) -> None:
        """Execute a load/partial plan's fetch with retries.  On success
        fills ``a.artifact/delay/nbytes/matched`` (wasted time from failed
        attempts folded into the delay); on exhaustion leaves ``a.artifact``
        None with the wasted time on ``a.delay`` and marks the record
        degraded — the caller falls back to exact recompute, so tokens are
        bit-identical to the fault-free run."""
        req, plan, entry = a.req, a.plan, a.lookup.entry
        nbytes = plan.fetch_bytes
        if self.cost_cfg is not self.cfg:
            nbytes = self._entry_fetch_bytes(entry, plan.matched_tokens)
        out, wasted, attempts = self._retry_fetch(
            req, tier=entry.tier, entry_id=entry.entry_id,
            matched=plan.matched_tokens, nbytes=nbytes,
            attempt_fn=lambda activity: self._fetch_kv(
                req, plan, a.lookup, activity=activity
            ),
            events=events,
        )
        if out is None:
            self.degraded_requests += 1
            a.rec.degraded = True
            a.artifact, a.nbytes, a.matched = None, 0.0, 0
            a.delay = wasted
            events.append(ev.DegradedToRecompute(
                t_s=self.clock.now, req_id=req.req_id, tier=entry.tier,
                entry_id=entry.entry_id, attempts=attempts, wasted_s=wasted,
                reason="fetch_exhausted",
            ))
            return
        artifact, delay, billed = out
        a.artifact, a.nbytes = artifact, billed
        a.delay = wasted + delay
        a.matched = plan.matched_tokens

    # -- marketplace: purchased KV --------------------------------------- #
    def _market_fetch(self, a: "_Admission", events: List[ev.Event]) -> None:
        """Execute a purchased plan (``ReusePlan.market``): delivery,
        verification, and settlement run inside the marketplace; on success
        a full-entry purchase is absorbed into this engine's own store so
        repeat requests become local hits; on ANY failure (seller gone,
        fetch error, failed verification) the request degrades to exact
        recompute — tokens stay bit-identical either way."""
        req, quote = a.req, a.plan.market
        res = self.market.execute(
            quote, req_id=req.req_id, now=self.clock.now,
            context_tokens=req.context_tokens, replica=self._replica,
        )
        events.extend(res.events)
        # the spot check ran on THIS engine's device: its GPU seconds are
        # real compute this request caused, charged win or lose
        a.rec.compute_cost += res.verify_cost
        if not res.ok:
            self.degraded_requests += 1
            self.market_failed += 1
            a.rec.degraded = True
            a.artifact, a.nbytes, a.matched = None, 0.0, 0
            a.delay = res.wasted_s
            events.append(ev.DegradedToRecompute(
                t_s=self.clock.now, req_id=req.req_id, tier=a.plan.tier,
                entry_id=quote.entry_id, attempts=1, wasted_s=res.wasted_s,
                reason=f"market:{res.reason}",
            ))
            return
        a.artifact = res.artifact
        a.nbytes = res.nbytes
        a.matched = res.matched_tokens
        a.delay = res.delay_s + res.verify_s
        self.market_purchases += 1
        self.market_spend += res.price
        if self.ec.store_write_back and res.matched_tokens >= quote.n_tokens:
            # full-entry purchase: absorb it locally (the artifact's rows
            # cover exactly the matched prefix, so the stored identity is
            # sound); partial matches are served but not stored
            ctx = list(req.context_tokens[:res.matched_tokens])
            saved = self._c_gpu_s * self.perf.t_prefill(self.cost_cfg, len(ctx))
            with self._attr("market_absorb", req.req_id):
                entry_id, _ = self.store.put(
                    ctx, res.artifact, tier=self._store_tier(),
                    saved_per_use=saved,
                )
            h = self.store.last_put_handle if entry_id is not None else None
            if h is not None and h.dedup:
                # the absorbed copy deduped against bytes already in the
                # shared core: book the zero-dollar KVShare credit for the
                # bytes the core did NOT have to duplicate
                self.market.note_dedup(
                    self.store.entries[entry_id].nbytes,
                    req_id=req.req_id, replica=self._replica,
                )
            self._emit_migrations(events)
            if entry_id is not None:
                e = self.store.entries[entry_id]
                events.append(ev.StoreWriteBack(
                    t_s=self.clock.now, req_id=req.req_id,
                    entry_id=entry_id, tier=e.tier, nbytes=e.nbytes,
                ))

    def market_spot_check(self, context_tokens, artifact, n_tokens: int):
        """Bit-exactness oracle for purchased KV: prefill the first
        ``n_tokens`` of the context fresh and compare the purchased rows
        exactly (both sides canonicalized through the same slot layout).
        Returns (ok, verify_s, verify_cost) — the sample prefill's modeled
        GPU seconds and dollars, which the caller charges to the request."""
        n = int(min(n_tokens, len(context_tokens)))
        if n <= 0:
            return True, 0.0, 0.0
        tokens = jnp.asarray([list(context_tokens[:n])], jnp.int32)
        temp = self.api.init_state(self.cfg, 1, self.ec.max_len)
        _, fresh = self._jit_prefill(self.params, tokens, temp)
        ref = paged.extract_slot(self.cfg, fresh, 0, n)
        temp = self.api.init_state(self.cfg, 1, self.ec.max_len)
        temp = paged.insert_slot(self.cfg, temp, 0, artifact, n_tokens=n)
        got = paged.extract_slot(self.cfg, temp, 0, n)
        ok = all(
            np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(
                jax.tree_util.tree_leaves(ref), jax.tree_util.tree_leaves(got)
            )
        )
        verify_s = self.perf.t_prefill(self.cost_cfg, n)
        return bool(ok), verify_s, self._c_gpu_s * verify_s

    def _write_back(self, req: Request, artifact: Any, events: List[ev.Event]) -> None:
        ctx = list(req.context_tokens)
        saved = self._c_gpu_s * self.perf.t_prefill(self.cost_cfg, len(ctx))
        tier = self._store_tier()
        with self._attr("write_back", req.req_id), span(
            "store.put", req=req.req_id, tier=tier
        ) as sp:
            entry_id, _ = self.store.put(ctx, artifact, tier=tier, saved_per_use=saved)
            if entry_id is not None:
                sp.set(nbytes=int(self.store.entries[entry_id].nbytes))
        h = self.store.last_put_handle if entry_id is not None else None
        if h is not None and h.dedup and self.market is not None:
            # KVShare multi-tenant dedup: another tenant already holds these
            # exact bytes in the shared core — settle the skipped upload as
            # a zero-dollar market credit carrying the SAVED bytes (the
            # handle's nbytes is bytes moved, which a dedup makes zero)
            self.market.note_dedup(
                self.store.entries[entry_id].nbytes,
                req_id=req.req_id, replica=self._replica,
            )
        if self.telemetry is not None and h is not None and h.dedup:
            # a content-addressed shared tier already held these bytes: no
            # upload happened, no fee accrued — record the dedup'd write-back
            # as an explicit zero-$ entry so the saving is visible per request
            self.telemetry.ledger.add(
                "transfer", "write_back_dedup", 0.0,
                replica=self._replica, req_id=req.req_id,
                tier=h.tier, nbytes=0.0, kind="store",
            )
        # capacity-pressure spills triggered by this put surface now, at
        # their own timestamp, not at the next step's drain
        self._emit_migrations(events)
        if entry_id is not None:
            e = self.store.entries[entry_id]
            events.append(
                ev.StoreWriteBack(
                    t_s=self.clock.now, req_id=req.req_id,
                    entry_id=entry_id, tier=e.tier, nbytes=e.nbytes,
                )
            )

    def _lookup(
        self, req: Request, pending: Optional[Dict[str, List[float]]] = None
    ) -> StoreLookup:
        """Consult the store about the request's context; quantify how much of
        it the architecture can actually consume.  A lookup already walked by
        the prefetch pass is carried forward (no second trie walk) as long as
        the store's trie has not mutated since.  ``pending`` — per-tier fetch
        bytes already planned by earlier batch-mates this admission instant,
        folded into the predicted queue wait."""
        if not self.ec.reuse_enabled:
            return StoreLookup.miss()
        cached = self._prefetch_lookup.pop(req.req_id, None)
        if cached is not None and cached[2] == self.store.trie_version:
            match = cached[0]
            entry = self.store.entries.get(cached[1]) if cached[1] else None
            self.lookup_reuses += 1
        else:
            match, entry = self.store.lookup(list(req.context_tokens))
            self.lookup_walks += 1
        partial_ok = paged.partial_reuse_allowed(self.cfg) and req.embeds is None
        unavailable = frozenset(
            t for t in self.store.tier_order
            if self.ec.faults is not None
            and self.ec.faults.browned_out(t, self.clock.now)
        )
        frac = 0.0
        n_ctx = len(req.context_tokens)
        if entry is not None and match.matched_tokens > 0:
            if match.matched_tokens >= n_ctx:
                frac = 1.0
            elif partial_ok:
                frac = match.matched_tokens / n_ctx
        queue_wait: Dict[str, float] = {}
        if entry is not None and frac > 0:
            # contended-link visibility for the planner: predicted queueing
            # delay on the entry's tier (0 on uncontended links)
            ahead = () if pending is None else tuple(pending.get(entry.tier, ()))
            wait = self.store.estimated_queue_wait(
                entry.tier,
                self._entry_fetch_bytes(entry, match.matched_tokens),
                pending=ahead,
            )
            if wait > 0:
                queue_wait[entry.tier] = wait
        composite = None
        fused_bytes: Dict[str, float] = {}
        if self._fusion_on and req.embeds is None and frac < 1.0:
            comp = self.store.lookup_composite(list(req.context_tokens))
            if comp.matched_tokens > 0 and not any(
                (e := self.store.entries.get(eid)) is not None
                and e.tier in unavailable
                for eid in comp.rows_by_entry()
            ):
                # a composite touching a browned-out tier is unplannable —
                # one dead source spoils the whole assembly
                composite = comp
                for eid, rows in comp.rows_by_entry().items():
                    src = self.store.entries.get(eid)
                    if src is None:
                        continue
                    fused_bytes[src.tier] = fused_bytes.get(src.tier, 0.0) + (
                        self._entry_fetch_bytes(src, rows)
                    )
                for t, b in fused_bytes.items():
                    # contended-link visibility for the fused option (and
                    # batch-mates planned behind it): predicted queueing
                    # delay for this tier's fused fetch
                    ahead = () if pending is None else tuple(pending.get(t, ()))
                    wait = self.store.estimated_queue_wait(t, b, pending=ahead)
                    if wait > 0:
                        queue_wait[t] = max(queue_wait.get(t, 0.0), wait)
        return StoreLookup(
            match=match, entry=entry, fraction=frac, partial_ok=partial_ok,
            queue_wait_s=queue_wait, composite=composite,
            fused_bytes_by_tier=fused_bytes, unavailable_tiers=unavailable,
        )

    def _entry_fetch_bytes(self, e, matched_tokens: int) -> float:
        """Bytes a fetch of ``matched_tokens`` moves, at economics scale."""
        if self.cost_cfg is not self.cfg:
            return s_storage_bytes(
                self.cost_cfg, matched_tokens,
                compression=0.5 if self.ec.compress_tier == e.tier else 1.0,
            )
        return e.nbytes * matched_tokens / max(e.n_tokens, 1)

    # ------------------------------------------------------------------ #
    # Execute: the two plan interpretations
    # ------------------------------------------------------------------ #
    def _execute_load(
        self, req: Request, a: "_Admission", events: List[ev.Event]
    ):
        """Insert the already-fetched stored context state (see
        ``_fetch_kv_resilient``), prefill only the unmatched tail + prompt."""
        entry = a.lookup.entry
        matched = a.matched
        temp = self.api.init_state(self.cfg, 1, self.ec.max_len)
        temp = paged.insert_slot(self.cfg, temp, 0, a.artifact, n_tokens=matched)
        ctx = list(req.context_tokens)
        tail = [] if req.embeds is not None else ctx[matched:]
        tokens = jnp.asarray([tail + list(req.prompt_tokens)], jnp.int32)
        logits, temp = self._prefill_launch(tokens, temp)
        prefill_s = self.perf.t_prefill(
            self.cost_cfg, len(tail) + len(req.prompt_tokens)
        )
        if self.ec.overlap_load:
            load_s = max(0.0, a.delay - prefill_s)
        else:
            load_s = a.delay
        events.append(
            ev.KVLoaded(
                t_s=self.clock.now, req_id=req.req_id,
                tier=(
                    entry.tier if entry is not None
                    else (a.plan.tier or "market")
                ),
                nbytes=a.nbytes, load_s=load_s, matched_tokens=matched,
            )
        )
        events.append(
            ev.PrefillDone(
                t_s=self.clock.now, req_id=req.req_id,
                n_tokens=len(tail) + len(req.prompt_tokens), prefill_s=prefill_s,
            )
        )
        return load_s, prefill_s, logits, temp

    def _prefill_launch(self, tokens: jax.Array, state, **kw):
        """One batch-1 prefill launch of the per-request path."""
        with span("engine.launch", program="prefill", q_len=int(tokens.shape[1])):
            return self._jit_prefill(self.params, tokens, state, **kw)

    def _execute_recompute(
        self, req: Request, plan: ReusePlan, events: List[ev.Event]
    ):
        """Full prefill; write the context state back iff the plan says so."""
        ctx, prompt = list(req.context_tokens), list(req.prompt_tokens)
        temp = self.api.init_state(self.cfg, 1, self.ec.max_len)

        def write_back(state):
            with span("engine.write_back", req=req.req_id):
                self._write_back(req, paged.extract_slot(self.cfg, state, 0, len(ctx)),
                                 events)

        if req.embeds is not None:
            # VLM/audio context: the context IS the embeddings. Single
            # phase — positions [0, ctx) of the state depend only on the
            # embeds, so the artifact is extractable post-hoc.
            tokens = jnp.asarray([prompt], jnp.int32)
            logits, temp = self._prefill_launch(tokens, temp, embeds=req.embeds)
            if plan.store_after:
                write_back(temp)
        elif plan.store_after:
            # Two-phase: context-only prefill -> snapshot (valid for SSM
            # state, which must not include prompt tokens) -> prompt.
            ctx_tokens = jnp.asarray([ctx], jnp.int32)
            _, temp = self._prefill_launch(ctx_tokens, temp)
            write_back(temp)
            tokens = jnp.asarray([prompt], jnp.int32)
            logits, temp = self._prefill_launch(tokens, temp)
        else:
            tokens = jnp.asarray([ctx + prompt], jnp.int32)
            logits, temp = self._prefill_launch(tokens, temp)
        prefill_s = self.perf.t_prefill(self.cost_cfg, len(ctx) + len(prompt))
        events.append(
            ev.PrefillDone(
                t_s=self.clock.now, req_id=req.req_id,
                n_tokens=len(ctx) + len(prompt), prefill_s=prefill_s,
            )
        )
        return prefill_s, logits, temp

    def _issue_prefetches(self) -> None:
        """Lookahead: start storage fetches for queued requests whose contexts
        are stored (the fetch streams while the engine computes)."""
        if self.ec.prefetch_lookahead <= 0 or not self.ec.reuse_enabled:
            return
        for nxt in self.queue.peek_arrived(self.clock.now, self.ec.prefetch_lookahead):
            if nxt.req_id in self._prefetch_ready:
                continue
            cached = self._prefetch_lookup.get(nxt.req_id)
            if cached is not None and cached[2] == self.store.trie_version:
                # an earlier pass already walked this context and the trie has
                # not mutated since — necessarily a miss (hits sit in
                # _prefetch_ready above), so there is nothing new to fetch
                continue
            m, e = self.store.lookup(list(nxt.context_tokens))
            self.lookup_walks += 1
            # carry this walk forward to admission (hits AND misses): the
            # admission-time lookup reuses it unless the trie mutated since
            self._prefetch_lookup[nxt.req_id] = (
                m, e.entry_id if e is not None else None, self.store.trie_version
            )
            if e is None or m.matched_tokens == 0:
                continue
            nbytes = self._entry_fetch_bytes(e, m.matched_tokens)
            delay = self.store.estimate_load_delay(e.tier, nbytes)
            self._prefetch_ready[nxt.req_id] = self.clock.now + delay
            # pin until admission consumes or abandons the prefetch: eviction
            # pressure (another request's write-back) and demotion must not
            # invalidate an in-flight fetch (ROADMAP prefetch/eviction race)
            self.store.pin(e.entry_id)
            self._prefetch_pins[nxt.req_id] = e.entry_id

    def _release_prefetch(self, req_id: int) -> None:
        """Admission consumed (or abandoned) this request's prefetch: drop the
        ready-time record and release the eviction pin."""
        self._prefetch_ready.pop(req_id, None)
        self._prefetch_lookup.pop(req_id, None)
        entry_id = self._prefetch_pins.pop(req_id, None)
        if entry_id is not None:
            self.store.unpin(entry_id)

    def packed_stats(self) -> Dict[str, Any]:
        """Packed-admission counters: jit bucket hit/miss, packing occupancy,
        trie-walk savings, and modeled admission busy time (the denominator
        of admission throughput)."""
        return {
            "jit": self.jit_stats.as_dict(),
            "batches": self.batches,
            "packed_q_tokens": self.packed_q_tokens,
            "packed_q_len": self.packed_q_len,
            "occupancy": self.packed_q_tokens / max(self.packed_q_len, 1),
            "lookup_walks": self.lookup_walks,
            "lookup_reuses": self.lookup_reuses,
            "admission_busy_s": self.admission_busy_s,
        }

    def decode_stats(self) -> Dict[str, Any]:
        """Decode-side counters: modeled decode busy time (the denominator of
        decode throughput), tokens decoded, and — under paged decode — block
        pool occupancy and cross-slot shared-block savings."""
        out: Dict[str, Any] = {
            "paged": self._paged_on,
            "decode_busy_s": self.decode_busy_s,
            "decode_tokens": self.decode_tokens,
        }
        if self._paged_on:
            ps = self._paged.stats()
            out.update(kv_block=ps.pop("block"), **ps)
        return out

    def fused_stats(self) -> Dict[str, Any]:
        """Fusion-path counters: fused admissions, reused-vs-recomputed
        context tokens (the realized CacheBlend ratio), distinct source
        entries fetched, modeled fused busy time, and the fused launch's own
        jit bucket hit/miss split."""
        return {
            "enabled": self._fusion_on,
            "admissions": self.fused_admissions,
            "reused_tokens": self.fused_reused_tokens,
            "recompute_tokens": self.fused_recompute_tokens,
            "sources": self.fused_sources,
            "busy_s": self.fused_busy_s,
            "jit": self.fused_jit.as_dict(),
        }

    def fault_stats(self) -> Dict[str, Any]:
        """Failure-handling counters: failed/retried fetch attempts, requests
        degraded to recompute, burned fetch time/bytes, store-side rollbacks
        and discards, plus the injector's own tally when one is wired."""
        out = {
            "fetch_failures": self.fetch_failures,
            "fetch_retries": self.fetch_retries,
            "degraded_requests": self.degraded_requests,
            "fetch_wasted_s": self.fetch_wasted_s,
            "fetch_wasted_bytes": self.fetch_wasted_bytes,
            "failed_puts": self.store.failed_puts,
            "discards": self.store.discards,
        }
        if self.ec.faults is not None:
            out["injector"] = self.ec.faults.stats()
        return out

    def _store_tier(self) -> str:
        if self.ec.store_tier is not None:
            return self.ec.store_tier
        return self.store.tier_order[-1]  # cloud tier (paper's EBS)

    # ------------------------------------------------------------------ #
    # Unified continuous-batching step (chunked prefill + decode)
    # ------------------------------------------------------------------ #
    def _step_unified(self) -> List[ev.Event]:
        """One unified scheduling step: intake admissible requests as chunk
        streams (plan + fetch + pool-block admission, no compute yet), then
        launch — decode rows co-scheduled with every ready prefill chunk in
        ONE kernel over the block pool.  Admission never monopolizes the
        device: a long suffix-prefill lands kv_block tokens at a time while
        in-flight decodes keep stepping in the same launches."""
        events: List[ev.Event] = []
        self._run_migrations(events)
        admitted = self._unified_intake(events)
        if self._unified_launch(events) or admitted:
            return events
        # idle: jump to the next actionable instant — the next arrival
        # (only if a slot could take it) or the earliest fetch completion.
        targets = [
            c.ready_s for c in self._chunks.values() if c.ready_s > self.clock.now
        ]
        nxt = self.queue.next_arrival()
        if nxt is not None and nxt > self.clock.now:
            targets.append(nxt)
        if not targets:
            return events  # fully drained
        self._advance_clock(min(targets), events)
        return events

    def _unified_intake(self, events: List[ev.Event]) -> bool:
        """Admit every admissible request with a free slot as a pending
        chunk stream: plan, execute the storage fetch (its delay becomes the
        stream's ready time — loads overlap other slots' compute for free),
        admit the slot's pool blocks up front and land any reused rows.
        No prefill compute happens here; chunks land in subsequent unified
        launches.  Requests the pool cannot carry (embeds) fall back to the
        legacy per-request admission."""
        free = [
            s for s in self.slots
            if not s.active and s.index not in self._chunks
        ]
        if not free:
            return False
        limit = min(len(free), self.ec.admit_batch or self.ec.max_slots)
        pending: Dict[str, List[float]] = {}
        admitted = False
        n = 0
        while n < limit:
            nxt = self.queue.peek_next(self.clock.now)
            if nxt is None:
                break
            slot = free[n]
            req = self.queue.pop_admissible(self.clock.now)
            if req.embeds is not None:
                self._admit_single(req, slot, events)
                n += 1
                admitted = True
                continue
            with span("engine.admit", n=1, req_ids=str(req.req_id)):
                a = self._plan_admission(req, slot, events, pending=pending)
                if a.plan.action == "fused":
                    for eid in a.plan.fused.source_entries:
                        if eid in self.store.entries:
                            self.store.pin(eid)
                            a.pins.append(eid)
                    for tier, b in a.lookup.fused_bytes_by_tier.items():
                        pending.setdefault(tier, []).append(b)
                if a.plan.loads_kv and a.lookup.entry is not None:
                    pending.setdefault(a.lookup.entry.tier, []).append(
                        self._entry_fetch_bytes(a.lookup.entry, a.plan.matched_tokens)
                    )
                self._start_chunk_stream(a, events)
            n += 1
            admitted = True
        if admitted:
            self._issue_prefetches()
        return admitted

    def _start_chunk_stream(self, a: "_Admission", events: List[ev.Event]) -> None:
        """Turn one planned admission into a pending chunk stream: fetch
        stored KV (prefix or fused sources), admit the slot's pool blocks
        for the full context+prompt, land the reused rows, and queue the
        remaining q tokens for chunked landing."""
        req, t0 = a.req, self.clock.now
        ctx, prompt = list(req.context_tokens), list(req.prompt_tokens)
        n_ctx, n_total = len(ctx), len(ctx) + len(prompt)
        ps = self._paged
        block = self.ec.kv_block

        fused_out = None
        if a.plan.action == "fused":
            fused_out = self._fetch_fused_sources(a, events)
        elif a.plan.market is not None:
            self._market_fetch(a, events)
            self._release_prefetch(req.req_id)
        elif a.plan.loads_kv and a.lookup.entry is not None:
            self._fetch_kv_resilient(a, events)
            self._release_prefetch(req.req_id)
        else:
            self._release_prefetch(req.req_id)

        own = ps.admit(a.slot.index, n_total)
        if fused_out is not None:
            sources, fetched = fused_out
            schedule = a.plan.fused
            layout = fusion.fused_layout(
                schedule, len(prompt),
                align=self.ec.pack_align, bucket_min=self.ec.pack_bucket_min,
            )
            row_bytes = self._kv_row_bytes
            with span("engine.assemble", q_len=layout.q_len, kv_len=layout.kv_len,
                      bucket_bytes=layout.kv_len * row_bytes,
                      stored_bytes=schedule.reused_tokens * row_bytes):
                caches = fusion.build_fused_caches(
                    self.cfg, schedule, sources, layout.kv_len
                )
            # land the whole assembled buffer's valid rows: reuse spans
            # carry stored (delta-RoPE'd) KV, recompute/prompt rows are
            # zero and get overwritten as their chunk tokens land
            rows = paged.block_rows(
                ps.tables[a.slot.index, : len(own)], block
            )[:n_total]
            with span("engine.land", req=req.req_id, n_tokens=n_total):
                self._pool_update(
                    rows,
                    (
                        (c.attn.k[:, 0, :n_total], c.attn.v[:, 0, :n_total])
                        for c in caches
                    ),
                )
            arrays = fusion.fused_arrays(schedule, ctx, prompt, layout)
            tokens = np.asarray(arrays["tokens"][0, : layout.n_q], np.int32)
            positions = np.asarray(arrays["q_pos"][0, : layout.n_q], np.int32)
            a.delay = max((d for _, _, d, _ in fetched), default=0.0)
            a.matched = schedule.reused_tokens
            for tier, nbytes, delay, rows_n in fetched:
                events.append(ev.KVLoaded(
                    t_s=t0, req_id=req.req_id, tier=tier, nbytes=nbytes,
                    load_s=delay, matched_tokens=rows_n,
                ))
            events.append(ev.FusedAdmitted(
                t_s=t0, req_id=req.req_id, slot=a.slot.index,
                reused_tokens=schedule.reused_tokens,
                recompute_tokens=schedule.recompute_tokens,
                n_spans=len(schedule.spans), n_sources=len(sources),
                q_len=layout.n_q, kv_len=n_total, jit_hit=True,
            ))
            self.fused_admissions += 1
            self.fused_reused_tokens += schedule.reused_tokens
            self.fused_recompute_tokens += schedule.recompute_tokens
            self.fused_sources += len(sources)
        elif a.artifact is not None:
            matched = a.matched
            rows = paged.block_rows(
                ps.tables[a.slot.index, : -(-matched // block)], block
            )[:matched]
            with span("engine.land", req=req.req_id, n_tokens=matched):
                self._pool_update(
                    rows,
                    (
                        (
                            jnp.asarray(c.attn.k[:, 0, :matched]),
                            jnp.asarray(c.attn.v[:, 0, :matched]),
                        )
                        for c in a.artifact.caches
                    ),
                )
            events.append(ev.KVLoaded(
                t_s=t0, req_id=req.req_id,
                tier=(
                    a.lookup.entry.tier if a.lookup.entry is not None
                    else (a.plan.tier or "market")
                ),
                nbytes=a.nbytes, load_s=a.delay, matched_tokens=matched,
            ))
            tokens = np.asarray(ctx[matched:] + prompt, np.int32)
            positions = np.arange(matched, n_total, dtype=np.int32)
        else:
            # plain recompute, or a degraded fetch falling back to exact
            # recompute (the burned time rides on a.delay -> ready_s)
            tokens = np.asarray(ctx + prompt, np.int32)
            positions = np.arange(0, n_total, dtype=np.int32)

        store_after = (
            a.plan.store_after and a.artifact is None and fused_out is None
        )
        if store_after:
            key = tuple(ctx)
            if key in self._wb_inflight:
                # a still-pending batch-mate already owes this context's
                # write-back (the packed batch's dedup, carried over)
                store_after = False
            else:
                self._wb_inflight[key] = a.slot.index
        self._chunks[a.slot.index] = _ChunkStream(
            a=a, tokens=tokens, positions=positions, n_ctx=n_ctx,
            ready_s=t0 + a.delay, store_after=store_after,
        )

    def _unified_launch(self, events: List[ev.Event]) -> bool:
        """Run one launch if there is anything to run: a mixed chunked
        launch when any chunk stream is ready, else a plain paged decode
        step (identical numerics, pricing and billing to legacy — the
        delegation anchor)."""
        now = self.clock.now
        ready = [
            self._chunks[i] for i in sorted(self._chunks)
            if self._chunks[i].ready_s <= now
        ]
        if not ready:
            if any(s.active for s in self.slots):
                self._decode_step(events)
                return True
            return False
        with span("engine.decode", n_active=sum(s.active for s in self.slots),
                  n_chunks=len(ready)):
            self._unified_mixed_step(ready, events)
        return True

    def _unified_mixed_step(
        self, ready: List[_ChunkStream], events: List[ev.Event]
    ) -> None:
        """ONE launch over the block pool mixing decode rows (every active
        slot, one token each — always granted) with prefill chunks of the
        ready streams (up to kv_block tokens each, under the step token
        budget).  Priced additively (PerfModel.t_step_unified: parameters
        stream once for the whole launch) and billed per row by normalized
        standalone-cost shares, so the step's dollars are conserved
        exactly."""
        ps = self._paged
        B, C = self.ec.max_slots, self.ec.kv_block
        t0 = self.clock.now
        decoding = [s for s in self.slots if s.active]
        splits = []
        for s in decoding:
            cow = ps.prepare_append(s.index)
            if cow is not None:
                splits.append(cow)
        if splits:
            self._copy_pool_blocks(splits)

        toks = np.zeros((B, C), np.int32)
        q_pos = np.full((B, C), -(2 ** 30), np.int32)
        last_idx = np.zeros((B,), np.int32)
        decode_lens = []
        for s in decoding:
            toks[s.index, 0] = s.last_token
            q_pos[s.index, 0] = int(ps.lens[s.index])
            decode_lens.append(
                s.record.context_len + s.record.prompt_len + s.generated
            )
        budget = max(self.ec.step_token_budget - len(decoding), 0)
        grants: List[tuple] = []  # (stream, n granted this step)
        chunk_desc: List[tuple] = []  # (n_new, L_end) for pricing
        for c in ready:
            g = min(C, c.remaining, budget)
            if g <= 0:
                if grants or decoding:
                    continue  # budget spent; this stream waits a step
                g = min(C, c.remaining)  # guarantee progress
            budget -= g
            sl = c.a.slot.index
            toks[sl, :g] = c.tokens[c.done : c.done + g]
            q_pos[sl, :g] = c.positions[c.done : c.done + g]
            last_idx[sl] = g - 1
            grants.append((c, g))
            chunk_desc.append((g, int(c.positions[c.done + g - 1]) + 1))

        jit_hit = self.unified_jit.record((B, C, ps.nb_max))
        with span("engine.launch", program="chunked_prefill", q_len=C,
                  jit_hit=int(jit_hit)):
            logits, self._pool_caches = self._jit_chunked(
                self.params, jnp.asarray(toks), self._pool_caches,
                jnp.asarray(ps.tables), jnp.asarray(q_pos), jnp.asarray(last_idx),
            )
        for s in decoding:
            ps.note_token(s.index)

        step_s = self.perf.t_step_unified(self.cost_cfg, decode_lens, chunk_desc)
        dec_sh, chk_sh = self.perf.step_unified_shares(
            self.cost_cfg, decode_lens, chunk_desc
        )
        self.clock.advance(step_s)
        n_chunk_tokens = sum(g for _, g in grants)
        self.unified_steps += 1
        self.unified_chunk_tokens += n_chunk_tokens
        self.unified_busy_s += step_s
        self.decode_tokens += len(decoding)
        dec_busy = step_s * sum(dec_sh)
        self.decode_busy_s += dec_busy
        self.admission_busy_s += step_s - dec_busy
        events.append(ev.UnifiedStep(
            t_s=t0, req_id=-1,
            req_ids=tuple(
                [s.request.req_id for s in decoding]
                + [c.a.req.req_id for c, _ in grants]
            ),
            n_decode=len(decoding), chunk_tokens=n_chunk_tokens,
            step_s=step_s, jit_hit=jit_hit,
        ))

        with span("engine.sync"):
            nxt_tok = np.asarray(jnp.argmax(logits, axis=-1))
        with span("engine.emit", n_tokens=len(decoding)):
            for s, share in zip(decoding, dec_sh):
                tok = int(nxt_tok[s.index])
                s.record.tokens.append(tok)
                s.record.decode_s += step_s
                s.record.compute_cost += self._c_gpu_s * step_s * share
                s.last_token = tok
                tok_ev = ev.TokenEmitted(
                    t_s=self.clock.now, req_id=s.request.req_id,
                    token=tok, index=s.generated,
                )
                events.append(tok_ev)
                if self.on_token is not None:
                    self.on_token(tok_ev)
                s.generated += 1
                self._maybe_finish(s, events)
        for (c, g), share in zip(grants, chk_sh):
            a = c.a
            a.rec.compute_cost += self._c_gpu_s * step_s * share
            c.done += g
            if c.remaining > 0:
                continue
            del self._chunks[a.slot.index]
            if self._wb_inflight.get(tuple(a.req.context_tokens)) == a.slot.index:
                self._wb_inflight.pop(tuple(a.req.context_tokens))
            if c.store_after:
                with span("engine.write_back", req=a.req.req_id) as sp:
                    art = _to_host(lambda: self._pool_slot_artifact(
                        a.slot.index, c.n_ctx), sp)
                    self._write_back(a.req, art, events)
            a.rec.matched_tokens = a.matched
            a.rec.load_s = a.delay
            # ttft_s = queue_s + load_s + prefill_s must equal the first
            # token's timeline instant: prefill_s absorbs the chunked
            # landing time INCLUDING the steps spent waiting on budget
            a.rec.prefill_s = max(0.0, self.clock.now - a.rec.start_s - a.delay)
            events.append(ev.PrefillDone(
                t_s=self.clock.now, req_id=a.req.req_id,
                n_tokens=len(c.tokens), prefill_s=a.rec.prefill_s,
            ))
            self._finish_admission(a, int(nxt_tok[a.slot.index]), events)

    def _pool_slot_artifact(self, slot: int, n_tokens: int) -> Any:
        """Gather a slot's first ``n_tokens`` pool rows as a standard
        batch-1 host artifact — the pool-side analogue of
        ``paged.extract_slot``, feeding the unified path's write-backs."""
        ps = self._paged
        block = self.ec.kv_block
        rows = paged.block_rows(
            ps.tables[slot, : -(-n_tokens // block)], block
        )[:n_tokens]
        return paged.LMState(
            pos=np.full((1,), n_tokens, np.int32),
            caches=tuple(
                paged.BlockCache(
                    paged.KVCache(
                        np.asarray(pc.attn.k[:, rows])[:, None],
                        np.asarray(pc.attn.v[:, rows])[:, None],
                    ),
                    None,
                )
                for pc in self._pool_caches
            ),
        )

    def unified_stats(self) -> Dict[str, Any]:
        """Unified-step counters: mixed launches run, prefill tokens landed
        through chunks, modeled mixed-launch busy time, and the launch's jit
        bucket hit/miss split (one static shape — steady unified serving
        must show exactly one miss)."""
        return {
            "enabled": self._unified_on,
            "steps": self.unified_steps,
            "chunk_tokens": self.unified_chunk_tokens,
            "busy_s": self.unified_busy_s,
            "jit": self.unified_jit.as_dict(),
        }

    # ------------------------------------------------------------------ #
    # Batched decode
    # ------------------------------------------------------------------ #
    def _decode_step(self, events: List[ev.Event]) -> None:
        active = np.array([s.active for s in self.slots])
        n_active = int(active.sum())
        with span("engine.decode", n_active=n_active):
            toks = np.array(
                [[s.last_token if s.active else 0] for s in self.slots], np.int32
            )
            if self._paged_on:
                logits = self._decode_paged_launch(toks)
            else:
                with span("engine.launch", program="decode"):
                    logits, self._state = self._jit_decode(
                        self.params, jnp.asarray(toks), self._state, jnp.asarray(active)
                    )
            lens = [
                s.record.context_len + s.record.prompt_len + s.generated
                for s in self.slots
                if s.active
            ]
            if self._paged_on:
                # live-blocks pricing: each slot is billed exactly the KV bytes
                # its block table streams, not the longest slot's padded length.
                step_s = self.perf.t_decode_paged(self.cost_cfg, lens)
            else:
                step_s = self.perf.t_decode(self.cost_cfg, 1, max(lens), batch=n_active)
            self.decode_busy_s += step_s
            self.decode_tokens += n_active
            self.clock.advance(step_s)
            if self._paged_on:
                # bill each slot proportional to the KV bytes its own live
                # blocks stream through the step, not an equal split — a
                # short-context slot no longer subsidizes a long batch-mate.
                # Uniform lengths give equal weights, so this agrees with the
                # dense split exactly in the uniform case.  The weights are
                # normalized, so the split conserves the step's dollars.
                w = [self.perf.decode_kv_bytes(self.cost_cfg, l) for l in lens]
                total_w = sum(w)
                costs = [self._c_gpu_s * step_s * wi / total_w for wi in w]
            else:
                costs = [self._c_gpu_s * step_s / n_active] * n_active

            with span("engine.sync"):
                nxt = np.asarray(jnp.argmax(logits, axis=-1))
            cost_it = iter(costs)
            with span("engine.emit", n_tokens=n_active):
                for s in self.slots:
                    if not s.active:
                        continue
                    tok = int(nxt[s.index])
                    s.record.tokens.append(tok)
                    s.record.decode_s += step_s
                    s.record.compute_cost += next(cost_it)
                    s.last_token = tok
                    tok_ev = ev.TokenEmitted(
                        t_s=self.clock.now, req_id=s.request.req_id,
                        token=tok, index=s.generated,
                    )
                    events.append(tok_ev)
                    if self.on_token is not None:
                        self.on_token(tok_ev)
                    s.generated += 1
                    self._maybe_finish(s, events)

    def _decode_paged_launch(self, toks: np.ndarray) -> jax.Array:
        """One paged decode launch across all active slots: grow/CoW-split
        block tables for the incoming token, run the shared-pool kernel, and
        append in place (tables/lens are host-side; shapes are static, so
        steady decode never recompiles)."""
        ps = self._paged
        splits = []
        for s in self.slots:
            if s.active:
                cow = ps.prepare_append(s.index)
                if cow is not None:
                    splits.append(cow)
        if splits:
            self._copy_pool_blocks(splits)
        with span("engine.launch", program="decode_paged"):
            logits, self._pool_caches = self._jit_decode_paged(
                self.params, jnp.asarray(toks), self._pool_caches,
                jnp.asarray(ps.tables), jnp.asarray(ps.lens, jnp.int32),
            )
        for s in self.slots:
            if s.active:
                ps.note_token(s.index)
        return logits

    def _maybe_finish(self, s: Slot, events: List[ev.Event]) -> None:
        req = s.request
        done = s.generated >= req.max_new_tokens or (
            req.eos_token is not None and s.last_token == req.eos_token
        )
        if done:
            s.record.finish_s = self.clock.now
            self.records.append(s.record)
            events.append(
                ev.RequestFinished(
                    t_s=self.clock.now, req_id=req.req_id, record=s.record
                )
            )
            s.active = False
            s.request = None
            if self._paged_on:
                # completion returns the slot's blocks to the shared pool
                # (shared-prefix blocks on their LAST reference) and zeroes
                # its table so stale writes land on the dump block.
                self._paged.free(s.index)
