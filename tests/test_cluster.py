"""Cluster serving: shared-cold-tier ownership (refcounts, dedup, crash
safety), router invariants, bloom-staleness tolerance, 1-replica golden
parity, and copy-then-keep rebalancing — deterministic + hypothesis."""
import json
from collections import Counter

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import test_serving as ts
from repro.core.perf_model import PerfModel, V100_X4_HF
from repro.core.pricing import AWS_PAPER
from repro.configs import get_config
from repro.kvcache.hierarchy import (
    HostMemoryBackend,
    SharedBackendCore,
    SharedTierBackend,
    TieredStore,
    TierSpec,
)
from repro.kvcache.transfer import SimClock, TransferModel
from repro.serving import (
    AffinityRouter,
    AlwaysReusePlanner,
    ClusterConfig,
    CostAwarePlanner,
    EngineConfig,
    Request,
    RoundRobinRouter,
    ServingCluster,
)
from repro.serving import events as ev
from repro.serving.router import BloomDigest, ReplicaView, RouteDecision


def _transfer():
    return TransferModel(PerfModel(V100_X4_HF), AWS_PAPER)


def _art(i, floats=150):
    return {"k": np.full((1, floats), i, np.float32)}  # 4*floats bytes


def _shared_stores(n=2, cap_gb=1.0):
    """N stores, each host_dram + a namespaced view onto ONE shared s3 core."""
    core = SharedBackendCore()
    stores = []
    for i in range(n):
        clock = SimClock()
        tr = _transfer()
        backends = {
            "host_dram": HostMemoryBackend(
                "host_dram", transfer=tr, clock=clock
            ),
            "s3": SharedTierBackend(
                "s3", core=core, namespace=f"r{i}", transfer=tr, clock=clock
            ),
        }
        stores.append(
            TieredStore(
                tiers=[TierSpec("host_dram", cap_gb), TierSpec("s3", cap_gb)],
                transfer=tr, clock=clock, chunk_tokens=4,
                pricing=AWS_PAPER, backends=backends,
            )
        )
    return core, stores


def check_core_invariants(core, stores):
    """The shared tier's conservation laws, checked after every mutation:
    refcounts equal live key counts, every key resolves, resident bytes are
    the sum over DISTINCT contents (dedup), and every store's own s3 entries
    stay readable — no replica can orphan another's entry."""
    cnt = Counter(core._keys.values())
    assert dict(core._refs) == dict(cnt)
    assert set(core._contents) == set(cnt)
    stats = core.stats()
    assert stats["resident_bytes"] == pytest.approx(
        sum(nb for _, nb in core._contents.values())
    )
    assert stats["logical_bytes"] >= stats["resident_bytes"]
    for s in stores:
        for eid, e in s.entries.items():
            if e.tier == "s3":
                assert s.backends["s3"]._read(eid) is not None


# --------------------------------------------------------------------------- #
# Shared cold tier: dedup, refcounted ownership, crash safety
# --------------------------------------------------------------------------- #
class TestSharedColdTier:
    def test_dedup_and_byte_conservation(self):
        core, (s0, s1) = _shared_stores(2)
        toks = list(range(8))
        e0, _ = s0.put(toks, _art(1), tier="s3")
        e1, _ = s1.put(toks, _art(1), tier="s3")  # identical content
        check_core_invariants(core, [s0, s1])
        st_ = core.stats()
        assert st_["n_keys"] == 2 and st_["n_contents"] == 1
        assert st_["dedup_hits"] == 1
        assert st_["logical_bytes"] == 2 * st_["resident_bytes"]
        # each replica is billed its own logical bytes regardless of dedup
        assert s0.tiers["s3"].used_bytes == s1.tiers["s3"].used_bytes

        # one replica evicts: the payload must survive for the other
        assert s0._evict_one("s3")
        check_core_invariants(core, [s0, s1])
        assert core.stats()["n_contents"] == 1
        art, h = s1.fetch(e1)
        assert art is not None and np.allclose(art["k"], 1.0)

        # last owner evicts: content is actually reclaimed
        assert s1._evict_one("s3")
        check_core_invariants(core, [s1])
        assert core.stats() == {
            "n_contents": 0, "n_keys": 0, "resident_bytes": 0,
            "logical_bytes": 0,
            "dedup_saved_bytes": core.stats()["dedup_saved_bytes"],
            "dedup_hits": 1,
        }

    def test_replica_crash_orphans_nothing(self):
        core, stores = _shared_stores(3)
        # overlapping working sets: ctx0 on all three, ctx1 on r0+r1, ctx2 r0
        ctxs = [list(range(i * 8, i * 8 + 8)) for i in range(3)]
        stores[0].put(ctxs[0], _art(0), tier="s3")
        stores[0].put(ctxs[1], _art(1), tier="s3")
        stores[0].put(ctxs[2], _art(2), tier="s3")
        stores[1].put(ctxs[0], _art(0), tier="s3")
        stores[1].put(ctxs[1], _art(1), tier="s3")
        stores[2].put(ctxs[0], _art(0), tier="s3")
        check_core_invariants(core, stores)
        assert core.stats()["n_contents"] == 3

        # r0 crashes out: its keys release, shared content survives
        released = stores[0].backends["s3"].release_namespace()
        assert released == 3
        check_core_invariants(core, stores[1:])
        assert core.stats()["n_contents"] == 2  # ctx2 died with its only owner
        for s, eids in ((stores[1], 2), (stores[2], 1)):
            assert len(s.entries) == eids
            for eid in s.entries:
                art, _ = s.fetch(eid)
                assert art is not None

    @settings(max_examples=25, deadline=None)
    @given(
        ops=st.lists(
            st.tuples(
                st.sampled_from(["put", "evict", "crash"]),
                st.integers(0, 1),  # store index
                st.integers(0, 4),  # context index
            ),
            min_size=1, max_size=30,
        )
    )
    def test_ops_conserve_shared_bytes(self, ops):
        """Any interleaving of puts / evictions / a namespace crash keeps the
        shared core's refcounts and byte accounting exact, and never makes a
        surviving store's entry unreadable."""
        core, stores = _shared_stores(2)
        crashed = [False, False]
        for op, si, ci in ops:
            s = stores[si]
            if crashed[si]:
                continue
            if op == "put":
                s.put(list(range(ci * 8, ci * 8 + 8)), _art(ci), tier="s3")
            elif op == "evict":
                s._evict_one("s3")
            else:
                s.backends["s3"].release_namespace()
                s.entries.clear()  # the replica is gone; drop its metadata
                for t in s.tiers.values():
                    t.used_bytes = 0.0
                crashed[si] = True
            live = [x for x, c in zip(stores, crashed) if not c]
            check_core_invariants(core, live)
        # terminal state: resident bytes exactly cover the distinct contents
        stats = core.stats()
        assert stats["resident_bytes"] == sum(
            nb for _, nb in core._contents.values()
        )


# --------------------------------------------------------------------------- #
# Router invariants
# --------------------------------------------------------------------------- #
def _affinity_router(n=3):
    r = AffinityRouter()
    r.configure(
        cost_cfg=get_config("llama-7b"), pricing=AWS_PAPER,
        perf=PerfModel(V100_X4_HF), chunk_tokens=16,
        replica_ids=list(range(n)),
    )
    return r


def _req(ctx=None):
    return Request(
        req_id=0, context_tokens=ctx or list(range(64)),
        prompt_tokens=list(range(8)), max_new_tokens=4,
    )


class TestRouterInvariants:
    @settings(max_examples=50, deadline=None)
    @given(
        frees=st.lists(st.integers(0, 3), min_size=2, max_size=5),
        loads=st.lists(st.integers(0, 6), min_size=5, max_size=5),
        with_digest=st.booleans(),
    )
    def test_never_routes_to_full_replica_when_another_has_room(
        self, frees, loads, with_digest
    ):
        n = len(frees)
        digest = None
        if with_digest:
            digest = BloomDigest()
            digest.update([f"h{i}" for i in range(4)])
        views = [
            ReplicaView(
                replica=i, load=loads[i % len(loads)], free_slots=frees[i],
                queue_s=0.1 * loads[i % len(loads)], digest=digest,
                hit_tier="host_dram",
            )
            for i in range(n)
        ]
        req = _req()
        for router in (_affinity_router(n), RoundRobinRouter()):
            d = router.decide(req, views)
            assert 0 <= d.replica < n
            if any(f > 0 for f in frees):
                assert frees[d.replica] > 0, (frees, d.replica)

    def test_full_replica_skipped_deterministic(self):
        """Deterministic mirror of the hypothesis property: replica 1 holds
        the whole context but has no free slot — both routers must divert to
        a replica with room."""
        ctx = list(range(64))
        holder = BloomDigest()
        from repro.kvcache.chunks import chunk_hash_chain

        holder.update(chunk_hash_chain(ctx, 16))
        views = [
            ReplicaView(replica=0, load=1, free_slots=1, digest=None,
                        hit_tier="host_dram"),
            ReplicaView(replica=1, load=4, free_slots=0, digest=holder,
                        hit_tier="host_dram", queue_s=0.2),
        ]
        req = _req(ctx)
        for router in (_affinity_router(2), RoundRobinRouter()):
            for _ in range(4):
                assert router.decide(req, views).replica == 0
        # when NO replica has room, the affinity pick comes back
        views_full = [
            ReplicaView(replica=0, load=4, free_slots=0, digest=None,
                        hit_tier="host_dram", queue_s=0.2),
            views[1],
        ]
        assert _affinity_router(2).decide(req, views_full).replica == 1

    def test_affinity_prefers_digest_owner_when_costs_allow(self):
        router = _affinity_router(2)
        ctx = list(range(64))
        holder = BloomDigest()
        from repro.kvcache.chunks import chunk_hash_chain

        holder.update(chunk_hash_chain(ctx, 16))
        views = [
            ReplicaView(replica=0, load=0, free_slots=2, digest=None,
                        hit_tier="host_dram"),
            ReplicaView(replica=1, load=0, free_slots=2, digest=holder,
                        hit_tier="host_dram"),
        ]
        d = router.decide(_req(ctx), views)
        assert d.replica == 1 and d.matched_tokens == 64

    def test_cold_cluster_coloates_on_ring_owner(self):
        """No digests yet: identical contexts must still pick the SAME
        replica (the consistent-hash owner), so the first write-back lands
        where future traffic will look for it."""
        router = _affinity_router(3)
        views = [
            ReplicaView(replica=i, load=0, free_slots=2) for i in range(3)
        ]
        ctx = list(range(64))
        picks = {router.decide(_req(ctx), views).replica for _ in range(5)}
        assert len(picks) == 1
        assert picks == {router.decide(_req(ctx), views).ring_owner}


# --------------------------------------------------------------------------- #
# Cluster end-to-end
# --------------------------------------------------------------------------- #
SPECS = [
    TierSpec("host_dram", 1.0),
    TierSpec("local_nvme", 1.0),
    TierSpec("s3", 1.0),
]


def _cluster_ec(**kw):
    # cost_arch: price routing/planning at llama-7b scale while the actual
    # compute is the reduced arch — on the paper's V100+AWS numbers a
    # host_dram hit strictly beats recompute, so affinity has something to
    # win (at toy scale recompute is always cheapest and the router would
    # correctly ignore the cache).
    base = dict(
        max_slots=2, max_len=128, chunk_tokens=16,
        tier_specs=SPECS, store_tier="host_dram", cost_arch="llama-7b",
    )
    base.update(kw)
    return EngineConfig(**base)


def _paper_hw():
    return dict(pricing=AWS_PAPER, perf=PerfModel(V100_X4_HF))


class TestClusterServing:
    def test_one_replica_golden_parity(self):
        """A 1-replica cluster behind the affinity router IS the engine: the
        golden seed trace replays action- and cost-identically through it."""
        golden = json.loads(ts.GOLDEN.read_text())
        cfg, params = ts._setup("llama-7b")
        for name, (reqs, kw) in ts._golden_scenarios(cfg, params).items():
            kw = dict(kw)
            planner = kw.pop("planner", None)
            ec = EngineConfig(max_slots=2, max_len=128, chunk_tokens=16, **kw)
            cl = ServingCluster(
                cfg, params,
                cluster_cfg=ClusterConfig(n_replicas=1),
                engine_cfg=ec,
                planner_factory=(lambda p=planner: p) if planner else None,
            )
            for r in reqs:
                cl.submit(Request(**r))
            s = cl.run()
            want = golden[name]
            recs = sorted(cl.replicas[0].records, key=lambda r: r.req_id)
            assert len(recs) == len(want["records"]), name
            for rec, w in zip(recs, want["records"]):
                assert rec.action == w["action"], (name, rec.req_id)
                assert rec.matched_tokens == w["matched_tokens"], (
                    name, rec.req_id)
                for field in ("load_s", "prefill_s", "decode_s", "start_s",
                              "finish_s", "compute_cost"):
                    assert getattr(rec, field) == pytest.approx(
                        w[field], abs=1e-9
                    ), (name, rec.req_id, field)
            got = cl.replicas[0].summary().as_dict()
            for k, v in want["summary"].items():
                assert got[k] == pytest.approx(v, abs=1e-9), (name, k)
            assert s.n_requests == len(want["records"])

    def test_bloom_false_positives_cost_but_never_corrupt(self):
        """Force EVERY digest probe to hit (the worst staleness/FP case):
        routing is mispriced, but the landing replica recomputes what it
        doesn't hold — generated tokens are identical to a bare engine's."""
        cfg, params = ts._setup("qwen2-0.5b")
        reqs = ts._requests(cfg, n=8, n_ctx=2, ctx_len=64, prompt_len=8,
                            new=4, seed=0)
        cl = ServingCluster(
            cfg, params,
            cluster_cfg=ClusterConfig(n_replicas=2, gossip_interval_s=0.0),
            engine_cfg=_cluster_ec(),
            planner_factory=AlwaysReusePlanner,
            **_paper_hw(),
        )
        lying = BloomDigest()
        lying._bits = (1 << lying.m) - 1  # every probe answers "present"
        lying.n_added = 1
        cl._digests = [lying, lying]
        for r in reqs:
            cl.submit(Request(**r))
        cl.run()
        routed = [e for _, e in cl.events
                  if isinstance(e, ev.RequestRouted)]
        assert routed and all(e.matched_tokens == 64 for e in routed)

        eng, _, tok_ref, _ = ts._run(
            cfg, params, reqs, planner=AlwaysReusePlanner(),
            tier_specs=SPECS, store_tier="host_dram",
        )
        tok_cl = {rec.req_id: rec.tokens for rec in cl.records}
        assert tok_cl == tok_ref

    def test_rebalance_moves_hot_entry_toward_traffic(self):
        """Copy-then-keep: traffic for a context concentrates on a replica
        that does not hold its KV; rebalancing copies the donor's bytes into
        the target's hot tier (event-verified) with the donor's copy alive
        throughout, and the target then serves loads locally."""
        cfg, params = ts._setup("qwen2-0.5b")
        ctx = list(range(64))
        prompt = list(range(100, 108))

        # materialize a valid stored artifact via a throwaway engine
        seed_req = dict(req_id=0, context_tokens=ctx, prompt_tokens=prompt,
                        max_new_tokens=4, arrival_s=0.0, expected_reuses=4)
        donor_eng, _, _, _ = ts._run(
            cfg, params, [seed_req], planner=AlwaysReusePlanner(),
            tier_specs=SPECS, store_tier="host_dram",
        )
        (eid, entry), = donor_eng.store.entries.items()
        art = donor_eng.store.backends[entry.tier].peek(eid)
        assert art is not None

        class ScriptedRouter:
            """Pin every request on replica 1 (the non-holder)."""

            def configure(self, **_):
                pass

            def decide(self, req, views):
                return RouteDecision(replica=1, matched_tokens=0,
                                     score=0.0, ring_owner=-1)

        cl = ServingCluster(
            cfg, params,
            cluster_cfg=ClusterConfig(
                n_replicas=2, gossip_interval_s=0.05,
                rebalance_interval_s=0.05, rebalance_min_hits=2,
            ),
            engine_cfg=_cluster_ec(store_write_back=False),
            router=ScriptedRouter(),
            planner_factory=AlwaysReusePlanner,
            **_paper_hw(),
        )
        # replica 0 holds the context; nothing ever writes back (the
        # cost-aware "local frequency below break-even" regime)
        ck = cl.replicas[0].store.content_key(ctx)
        e0, _ = cl.replicas[0].store.put(
            ctx, art, tier="host_dram", saved_per_use=entry.saved_per_use
        )
        assert e0 is not None

        for i, t in enumerate((0.1, 0.4, 0.7)):
            cl.submit(Request(
                req_id=i, context_tokens=ctx, prompt_tokens=prompt,
                max_new_tokens=4, arrival_s=t, expected_reuses=4,
            ))
        cl.run()

        reb = [e for _, e in cl.events if isinstance(e, ev.ReplicaRebalanced)]
        assert len(reb) == 1 and cl.rebalances == 1
        r = reb[0]
        assert (r.from_replica, r.to_replica, r.content_key) == (0, 1, ck)
        # no unreachable window: the donor's copy survived the whole run...
        assert cl.replicas[0].store.entries[e0].content_key == ck
        # ...and the target now holds its own hot-tier copy
        tgt = [e for e in cl.replicas[1].store.entries.values()
               if e.content_key == ck]
        assert len(tgt) == 1 and tgt[0].tier == "host_dram"
        # the copy landed between arrivals: the last request LOADED locally
        recs = sorted(cl.replicas[1].records, key=lambda x: x.req_id)
        assert [x.action for x in recs][:1] == ["recompute"]
        assert recs[-1].action == "load" and recs[-1].matched_tokens == 64

    def test_affinity_beats_round_robin_on_hit_rate(self):
        """The economics headline at fleet scale: affinity routing keeps each
        context's traffic on one replica, so aggregate hit rate strictly
        beats cache-oblivious round-robin on a skewed reuse workload."""
        cfg, params = ts._setup("qwen2-0.5b")
        reqs = ts._requests(cfg, n=16, n_ctx=3, ctx_len=64, prompt_len=8,
                            new=4, seed=1)
        # spread arrivals so capacity pressure never overrides affinity
        for i, r in enumerate(reqs):
            r["arrival_s"] = i * 0.2

        def run(router):
            cl = ServingCluster(
                cfg, params,
                cluster_cfg=ClusterConfig(
                    n_replicas=2, gossip_interval_s=0.05
                ),
                engine_cfg=_cluster_ec(),
                router=router,
                planner_factory=AlwaysReusePlanner,
                **_paper_hw(),
            )
            for r in reqs:
                cl.submit(Request(**r))
            return cl, cl.run()

        cl_a, s_a = run(None)  # AffinityRouter default
        cl_r, s_r = run(RoundRobinRouter())
        assert s_a.n_requests == s_r.n_requests == 16
        assert s_a.hit_rate > s_r.hit_rate, (s_a.hit_rate, s_r.hit_rate)
        # identical tokens either way (routing never changes outputs)
        tok_a = {r.req_id: r.tokens for r in cl_a.records}
        tok_r = {r.req_id: r.tokens for r in cl_r.records}
        assert tok_a == tok_r

    def test_remove_replica_releases_only_its_shared_keys(self):
        cfg, params = ts._setup("qwen2-0.5b")
        cl = ServingCluster(
            cfg, params,
            cluster_cfg=ClusterConfig(n_replicas=2),
            engine_cfg=_cluster_ec(store_tier="s3"),
            **_paper_hw(),
        )
        ctx0, ctx1 = list(range(64)), list(range(64, 128))
        cl.replicas[0].store.put(ctx0, _art(0), tier="s3")
        cl.replicas[1].store.put(ctx0, _art(0), tier="s3")  # dedup'd twin
        cl.replicas[1].store.put(ctx1, _art(1), tier="s3")
        assert cl.core.stats() == dict(
            cl.core.stats(), n_keys=3, n_contents=2, dedup_hits=1
        )
        released = cl.remove_replica(0)
        assert released == 1
        stats = cl.core.stats()
        assert stats["n_keys"] == 2 and stats["n_contents"] == 2
        for eid in cl.replicas[1].store.entries:
            art, _ = cl.replicas[1].store.fetch(eid)
            assert art is not None
        # the removed replica is invisible to routing and the idle predicate
        assert all(v.replica == 1 for v in cl.views())
        assert cl.idle


# --------------------------------------------------------------------------- #
# Delta gossip: incremental digests are bit-identical to full rebuilds
# --------------------------------------------------------------------------- #
class TestDeltaGossip:
    def _check_equiv(self, cl):
        """The staleness-equivalence invariant: after any gossip tick, each
        live replica's incrementally-maintained digest has EXACTLY the bits
        a from-scratch rebuild over the store's current hash surface would
        produce — delta shipping changes the wire bytes, never the answer."""
        for i, eng in enumerate(cl.replicas):
            if not cl._alive[i]:
                continue
            fresh = BloomDigest(cl.cc.digest_bits, cl.cc.digest_hashes)
            fresh.update(eng.store.digest_hashes())
            assert cl._digests[i]._bits == fresh._bits, i

    def test_delta_ticks_equal_full_rebuild(self):
        cfg, params = ts._setup("qwen2-0.5b")
        cl = ServingCluster(
            cfg, params,
            cluster_cfg=ClusterConfig(n_replicas=2),
            engine_cfg=_cluster_ec(),
            **_paper_hw(),
        )
        store = cl.replicas[0].store

        cl.gossip_now()  # first tick: both replicas full-sync from scratch
        self._check_equiv(cl)
        base_full = cl.gossip_full_syncs
        assert base_full == 2

        # put-only window: every tick ships only the add-set, no resyncs
        eids = []
        for j in range(4):
            eid, _ = store.put(
                [j * 50 + k for k in range(32)], _art(j), tier="host_dram"
            )
            eids.append(eid)
            cl.gossip_now()
            self._check_equiv(cl)
        assert cl.gossip_full_syncs == base_full
        assert cl.gossip_delta_hashes > 0

        # a removal (discard) bumps the digest epoch: bloom bits cannot be
        # cleared, so the next tick full-rebuilds — and stays exact
        assert store.discard(eids[1])
        cl.gossip_now()
        self._check_equiv(cl)
        assert cl.gossip_full_syncs == base_full + 1

        # an eviction is a removal too
        assert store._evict_one("host_dram")
        cl.gossip_now()
        self._check_equiv(cl)
        assert cl.gossip_full_syncs == base_full + 2

        # and after a resync, deltas resume
        deltas = cl.gossip_delta_hashes
        store.put(list(range(900, 932)), _art(9), tier="host_dram")
        cl.gossip_now()
        self._check_equiv(cl)
        assert cl.gossip_full_syncs == base_full + 2
        assert cl.gossip_delta_hashes > deltas

    def test_quiescent_ticks_ship_nothing(self):
        """No store mutations between ticks => no hashes, no resyncs (the
        steady-state wire cost of gossip is zero)."""
        cfg, params = ts._setup("qwen2-0.5b")
        cl = ServingCluster(
            cfg, params,
            cluster_cfg=ClusterConfig(n_replicas=2),
            engine_cfg=_cluster_ec(),
            **_paper_hw(),
        )
        cl.replicas[0].store.put(list(range(32)), _art(0), tier="host_dram")
        cl.gossip_now()
        full, deltas = cl.gossip_full_syncs, cl.gossip_delta_hashes
        for _ in range(3):
            cl.gossip_now()
            self._check_equiv(cl)
        assert cl.gossip_full_syncs == full
        assert cl.gossip_delta_hashes == deltas


def test_cluster_replica_devices():
    """``devices=`` commits replica i's params and KV state to devices[i]
    and serves what the default placement serves; a device count that does
    not match the replica count is refused."""
    import jax

    cfg, params = ts._setup("qwen2-0.5b")
    reqs = ts._requests(cfg, n=6, n_ctx=2, ctx_len=64, prompt_len=8, new=4, seed=0)
    dev = jax.devices()[0]

    def served(devices):
        cl = ServingCluster(
            cfg, params, cluster_cfg=ClusterConfig(n_replicas=2),
            engine_cfg=_cluster_ec(), planner_factory=AlwaysReusePlanner,
            devices=devices, **_paper_hw(),
        )
        for r in reqs:
            cl.submit(Request(**r))
        cl.run()
        return cl, {rec.req_id: rec.tokens for rec in cl.records}

    placed, tokens = served([dev, dev])
    for e in placed.replicas:
        assert e.device == dev
        leaves = jax.tree_util.tree_leaves((e.params, e._state))
        assert all(leaf.devices() == {dev} for leaf in leaves)
    assert tokens == served(None)[1]
    with pytest.raises(ValueError, match="1 devices for 2 replicas"):
        ServingCluster(cfg, params, cluster_cfg=ClusterConfig(n_replicas=2),
                       engine_cfg=_cluster_ec(), devices=[dev])
