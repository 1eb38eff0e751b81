"""Context-state extraction/insertion between batched device state and the
storage tier.

The device-side cache is slotted-dense (DESIGN.md §3): one batch slot per
active sequence.  The storage-side artifact for a context of L tokens is the
per-slot slice of the context state:

  * attention layers — K/V rows [0, L)                      (O(L) bytes)
  * Mamba/SSD layers — (conv tail, SSD state)               (O(1) bytes)
  * enc-dec          — encoder-output cross-attention KV    (O(L_enc) bytes)

Artifacts are host numpy pytrees (storage is host/remote by definition);
``insert_slot`` is the load path back into a batched device state.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models.attention import KVCache
from repro.models.blocks import BlockCache
from repro.models.encdec import EncDecState
from repro.models.lm import LMState
from repro.models.ssm import MambaState


def _np(tree):
    return jax.tree_util.tree_map(np.asarray, tree)


# --------------------------------------------------------------------------- #
# Extract: batched device state -> per-context host artifact
# --------------------------------------------------------------------------- #
def extract_slot(cfg: ArchConfig, state: Any, slot: int, length: int) -> Any:
    """Pull slot ``slot``'s first ``length`` tokens of context state."""
    if isinstance(state, EncDecState):
        return _np(
            EncDecState(
                # context is the audio: the decoder restarts at pos 0 on reuse
                pos=jnp.zeros((1,), jnp.int32),
                # decoder self-KV is per-request (prompt side), not context
                self_kv=KVCache(
                    state.self_kv.k[:, slot : slot + 1, :0],
                    state.self_kv.v[:, slot : slot + 1, :0],
                ),
                cross_kv=KVCache(
                    state.cross_kv.k[:, slot : slot + 1],
                    state.cross_kv.v[:, slot : slot + 1],
                ),
            )
        )
    assert isinstance(state, LMState)

    def per_cache(c: BlockCache) -> BlockCache:
        if c.attn is not None:
            return BlockCache(
                KVCache(
                    c.attn.k[:, slot : slot + 1, :length],
                    c.attn.v[:, slot : slot + 1, :length],
                ),
                None,
            )
        return BlockCache(
            None,
            MambaState(
                conv=c.mamba.conv[:, slot : slot + 1],
                ssd=c.mamba.ssd[:, slot : slot + 1],
            ),
        )

    return _np(
        LMState(
            pos=jnp.full((1,), length, jnp.int32),
            caches=tuple(per_cache(c) for c in state.caches),
        )
    )


# --------------------------------------------------------------------------- #
# Insert: host artifact -> slot of a batched device state
# --------------------------------------------------------------------------- #
def insert_slot(
    cfg: ArchConfig, state: Any, slot: int, artifact: Any, n_tokens: int = None
) -> Any:
    """Write a stored context into batch slot ``slot``; returns the new state
    with ``pos[slot]`` set to the artifact's token count (or ``n_tokens`` for
    a partial-prefix insert of attention KV)."""
    art_pos = int(np.asarray(artifact.pos)[0])
    L = art_pos if n_tokens is None else min(n_tokens, art_pos)

    if isinstance(state, EncDecState):
        assert isinstance(artifact, EncDecState)
        ck = state.cross_kv
        new_cross = KVCache(
            ck.k.at[:, slot].set(jnp.asarray(artifact.cross_kv.k[:, 0], ck.k.dtype)),
            ck.v.at[:, slot].set(jnp.asarray(artifact.cross_kv.v[:, 0], ck.v.dtype)),
        )
        # self-KV prefix (0 rows for a stored context artifact; the prompt's
        # rows when installing a freshly prefilled batch-1 state).
        sk = state.self_kv
        L_self = artifact.self_kv.k.shape[2]
        if L_self > 0:
            sk = KVCache(
                jax.lax.dynamic_update_slice(
                    sk.k,
                    jnp.asarray(artifact.self_kv.k[:, :, :L_self], sk.k.dtype),
                    (0, slot, 0, 0, 0),
                ),
                jax.lax.dynamic_update_slice(
                    sk.v,
                    jnp.asarray(artifact.self_kv.v[:, :, :L_self], sk.v.dtype),
                    (0, slot, 0, 0, 0),
                ),
            )
        return EncDecState(
            pos=state.pos.at[slot].set(artifact.pos[0]),
            self_kv=sk,
            cross_kv=new_cross,
        )

    assert isinstance(state, LMState) and isinstance(artifact, LMState)

    def per_cache(c: BlockCache, a: BlockCache) -> BlockCache:
        if c.attn is not None:
            ak = jnp.asarray(a.attn.k[:, 0, :L], c.attn.k.dtype)
            av = jnp.asarray(a.attn.v[:, 0, :L], c.attn.v.dtype)
            return BlockCache(
                KVCache(
                    jax.lax.dynamic_update_slice(
                        c.attn.k, ak[:, None], (0, slot, 0, 0, 0)
                    ),
                    jax.lax.dynamic_update_slice(
                        c.attn.v, av[:, None], (0, slot, 0, 0, 0)
                    ),
                ),
                None,
            )
        # SSM state is all-or-nothing (O(1) snapshot at full context length).
        return BlockCache(
            None,
            MambaState(
                conv=c.mamba.conv.at[:, slot].set(
                    jnp.asarray(a.mamba.conv[:, 0], c.mamba.conv.dtype)
                ),
                ssd=c.mamba.ssd.at[:, slot].set(
                    jnp.asarray(a.mamba.ssd[:, 0], c.mamba.ssd.dtype)
                ),
            ),
        )

    return LMState(
        pos=state.pos.at[slot].set(L),
        caches=tuple(per_cache(c, a) for c, a in zip(state.caches, artifact.caches)),
    )


def partial_reuse_allowed(cfg: ArchConfig) -> bool:
    """Partial-prefix reuse needs per-position state (attention KV).  SSM /
    hybrid / enc-dec store O(1)-or-encoder state snapshots at full context
    length only => all-or-nothing (DESIGN.md §6)."""
    return cfg.family in ("dense", "moe", "vlm") and cfg.n_ssm_layers == 0


# --------------------------------------------------------------------------- #
# Packed ragged prefill: layout + multi-slot insertion
# --------------------------------------------------------------------------- #
def packable_arch(cfg: ArchConfig, max_len: int) -> bool:
    """Whether batched admission may pack this arch's suffix-prefills into one
    ragged sequence.  Requires per-position attention state (no SSM/enc-dec
    sequence mixing) and a non-ring KV cache: when ``sliding_window <
    max_len`` the slot cache is a ring buffer whose prefill path attends
    [old ring ++ new KV] — a layout a packed buffer cannot reproduce
    bit-exactly — so SWA archs ride the per-request path."""
    return (
        cfg.family in ("dense", "moe", "vlm")
        and cfg.n_ssm_layers == 0
        and not (cfg.sliding_window and cfg.sliding_window < max_len)
    )


@dataclasses.dataclass(frozen=True)
class PackSegment:
    """One request's span of the packed sequence (all indices host-static)."""

    slot: int  # batch slot the outputs scatter back into
    kv_start: int  # first packed kv row of this segment (align-multiple)
    q_start: int  # first packed q index of this segment's new tokens
    matched: int  # reused prefix rows preloaded at [kv_start, kv_start+matched)
    n_new: int  # new (tail + prompt) tokens prefilled by the kernel
    n_total: int  # matched + n_new == rows valid after prefill

    @property
    def q_last(self) -> int:
        return self.q_start + self.n_new - 1


@dataclasses.dataclass(frozen=True)
class PackLayout:
    """Packed-sequence geometry for one admission batch.

    kv spans are aligned to ``align`` (the flash kernel's kv block): every
    segment starts at an align-multiple, so cross-segment kv blocks are
    fully masked exact no-ops and the packed attention is bit-identical to
    per-request attention (tests/test_packed.py).  The q side is
    padding-free: new-token runs concatenate densely and only the total pads
    up to the jit bucket."""

    segments: Tuple[PackSegment, ...]
    q_len: int  # bucketed total q length
    kv_len: int  # bucketed total kv length
    q_tokens: int  # sum of n_new (un-padded)

    @property
    def occupancy(self) -> float:
        """Useful fraction of the padded q sequence the kernel runs over."""
        return self.q_tokens / max(self.q_len, 1)


def pack_bucket(n: int, minimum: int = 16) -> int:
    """Round up to a power-of-two jit bucket so steady-state serving reuses
    compiled shapes instead of recompiling per ragged length."""
    b = max(minimum, 1)
    while b < n:
        b *= 2
    return b


def pack_layout(
    slots: List[int],
    matched: List[int],
    n_new: List[int],
    *,
    align: int = 128,
    bucket_min: int = 16,
) -> PackLayout:
    segs: List[PackSegment] = []
    kv_off = 0
    q_off = 0
    for slot, m, n in zip(slots, matched, n_new):
        total = m + n
        segs.append(
            PackSegment(
                slot=slot, kv_start=kv_off, q_start=q_off,
                matched=m, n_new=n, n_total=total,
            )
        )
        kv_off += -(-total // align) * align
        q_off += n
    return PackLayout(
        segments=tuple(segs),
        q_len=pack_bucket(q_off, bucket_min),
        kv_len=pack_bucket(kv_off, max(align, bucket_min)),
        q_tokens=q_off,
    )


def _attn_kinds(cfg: ArchConfig):
    from repro.models import blocks as blocks_mod

    kinds = blocks_mod.block_kinds(cfg)
    assert all(k.mixer == "a" for k in kinds), (cfg.name, kinds)
    return kinds, cfg.n_layers // len(kinds)


def build_packed_caches(
    cfg: ArchConfig, layout: PackLayout, artifacts: List[Any], dtype=None
) -> Any:
    """Packed per-layer KV buffers with every segment's reused prefix rows
    preloaded at its kv span — the multi-slot insertion of the load path.
    ``artifacts[i]`` is segment i's stored LMState (or None for recompute);
    assembly happens host-side in one numpy pass, then lands on device as a
    single transfer."""
    from repro.models import common as common_mod
    from repro.models.blocks import BlockCache
    from repro.obs.host import span  # here: obs imports serving, which imports kvcache

    kinds, n_periods = _attn_kinds(cfg)
    dtype = dtype or common_mod.resolve_dtype(cfg.dtype)
    np_dtype = np.dtype(jnp.zeros((), dtype).dtype.name)
    shape = (n_periods, 1, layout.kv_len, cfg.n_kv_heads, cfg.resolved_head_dim)

    out = []
    for ki in range(len(kinds)):
        k_buf = np.zeros(shape, np_dtype)
        v_buf = np.zeros(shape, np_dtype)
        for seg, art in zip(layout.segments, artifacts):
            if art is None or seg.matched <= 0:
                continue
            rows = slice(seg.kv_start, seg.kv_start + seg.matched)
            k_buf[:, :, rows] = np.asarray(
                art.caches[ki].attn.k[:, :, : seg.matched], np_dtype
            )
            v_buf[:, :, rows] = np.asarray(
                art.caches[ki].attn.v[:, :, : seg.matched], np_dtype
            )
        with span("engine.h2d", nbytes=k_buf.nbytes + v_buf.nbytes):
            out.append(BlockCache(KVCache(jnp.asarray(k_buf), jnp.asarray(v_buf)), None))
    return tuple(out)


def pack_arrays(layout: PackLayout, new_tokens: List[List[int]]) -> dict:
    """Host-side int32 index arrays driving the packed kernel: tokens,
    segment-local q/kv positions, segment ids, kv landing rows, and each
    segment's last-q index (padded with 0 — callers ignore extra rows)."""
    Sq, Skv = layout.q_len, layout.kv_len
    tokens = np.zeros((1, Sq), np.int32)
    q_pos = np.full((1, Sq), -(2**30), np.int32)
    q_seg = np.full((1, Sq), -1, np.int32)
    q_rows = np.full((1, Sq), Skv, np.int32)  # padding lands on the scratch row
    kv_pos = np.full((1, Skv), -1, np.int32)
    kv_seg = np.full((1, Skv), -2, np.int32)
    for i, (seg, toks) in enumerate(zip(layout.segments, new_tokens)):
        assert len(toks) == seg.n_new, (len(toks), seg)
        q = slice(seg.q_start, seg.q_start + seg.n_new)
        tokens[0, q] = toks
        q_pos[0, q] = np.arange(seg.matched, seg.n_total, dtype=np.int32)
        q_seg[0, q] = i
        q_rows[0, q] = np.arange(
            seg.kv_start + seg.matched, seg.kv_start + seg.n_total, dtype=np.int32
        )
        rows = slice(seg.kv_start, seg.kv_start + seg.n_total)
        kv_pos[0, rows] = np.arange(seg.n_total, dtype=np.int32)
        kv_seg[0, rows] = i
    return {
        "tokens": tokens, "q_pos": q_pos, "q_seg": q_seg, "q_rows": q_rows,
        "kv_pos": kv_pos, "kv_seg": kv_seg,
    }


# --------------------------------------------------------------------------- #
# Shared KV block pool: paged batched decode state
# --------------------------------------------------------------------------- #
KV_BLOCK = 128  # pool block size in tokens (== the flash kernels' kv block)


class BlockPool:
    """Host-side bookkeeping for the shared device KV block pool.

    Block ids index a single device array of ``n_blocks * block`` KV rows
    shared by every batch slot.  Block 0 is the reserved *dump* block: a slot
    whose block table is zeroed (freed/inactive) computes its decode write
    row inside block 0, so a stale slot can never corrupt a block that has
    been recycled to another sequence.

    Blocks are reference-counted so batch-mates that loaded the same stored
    context can point their table prefixes at ONE copy of the shared-prefix
    blocks (write-back dedup carried into the pool).  ``release`` returns a
    block to the free list exactly once — when its last reference drops —
    and ``PagedSlots.prepare_append`` is the copy-on-write primitive:
    appending into a shared boundary block first splits it onto a fresh
    private block.  ``tests/test_paged_decode.py`` drives these invariants
    with hypothesis.
    """

    def __init__(self, n_blocks: int, block: int = KV_BLOCK):
        assert n_blocks >= 2, "need the dump block plus at least one real block"
        self.block = block
        self.n_blocks = n_blocks
        self.ref = np.zeros(n_blocks, np.int64)
        self.ref[0] = 1  # dump block: permanently held by the pool itself
        self._free: List[int] = list(range(n_blocks - 1, 0, -1))

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        """Distinct non-dump blocks currently referenced."""
        return self.n_blocks - 1 - len(self._free)

    def alloc(self, n: int) -> List[int]:
        assert n <= len(self._free), (n, len(self._free))
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            assert self.ref[b] == 0, b
            self.ref[b] = 1
        return out

    def share(self, bid: int) -> int:
        assert 0 < bid < self.n_blocks and self.ref[bid] > 0, bid
        self.ref[bid] += 1
        return bid

    def release(self, bid: int) -> None:
        assert 0 < bid < self.n_blocks and self.ref[bid] > 0, bid
        self.ref[bid] -= 1
        if self.ref[bid] == 0:
            self._free.append(bid)

    def free_list(self) -> List[int]:
        return list(self._free)


@dataclasses.dataclass(frozen=True)
class CowSplit:
    """A copy-on-write split: pool rows of ``src`` must be device-copied to
    ``dst`` before the next write touches the block."""

    src: int
    dst: int


class PagedSlots:
    """Block tables + live lengths for a batch of slots over one BlockPool.

    The engine's host-side view of the paged decode state: per-slot tables
    (0-padded, fixed width ``max_len // block`` so every decode launch has
    one static shape), live token counts, and the alloc/share/append/free
    lifecycle.  Device arrays are the engine's; this class only decides
    which pool blocks hold what.
    """

    def __init__(self, n_slots: int, max_len: int, block: int = KV_BLOCK):
        assert max_len % block == 0, (max_len, block)
        self.block = block
        self.nb_max = max_len // block
        # worst case every slot fills max_len with private blocks (+ dump)
        self.pool = BlockPool(1 + n_slots * self.nb_max, block)
        self.tables = np.zeros((n_slots, self.nb_max), np.int32)
        self.lens = np.zeros(n_slots, np.int64)
        self.n_blocks = np.zeros(n_slots, np.int64)  # table entries in use
        self.live = np.zeros(n_slots, bool)
        self.shared_block_hits = 0  # blocks deduped across batch-mates
        self.pool_blocks_peak = 0  # high-water distinct blocks in use

    def admit(
        self,
        slot: int,
        n_total: int,
        *,
        shared_from: Optional[int] = None,
        shared_blocks: int = 0,
    ) -> List[int]:
        """Allocate the slot's table for ``n_total`` live rows; the first
        ``shared_blocks`` entries alias slot ``shared_from``'s (same stored
        context, write-back dedup).  Returns the NEWLY allocated block ids —
        the ones whose rows the caller must fill; shared blocks already hold
        the right rows."""
        assert not self.live[slot], slot
        nb = -(-n_total // self.block)
        assert 0 < nb <= self.nb_max, (n_total, self.nb_max)
        assert shared_blocks <= nb
        if shared_blocks:
            assert shared_from is not None and self.live[shared_from]
            assert shared_blocks <= self.n_blocks[shared_from]
            for j in range(shared_blocks):
                self.tables[slot, j] = self.pool.share(
                    int(self.tables[shared_from, j])
                )
            self.shared_block_hits += shared_blocks
        own = self.pool.alloc(nb - shared_blocks)
        self.tables[slot, shared_blocks:nb] = own
        self.tables[slot, nb:] = 0
        self.lens[slot] = n_total
        self.n_blocks[slot] = nb
        self.live[slot] = True
        self.pool_blocks_peak = max(self.pool_blocks_peak, self.pool.n_used)
        return own

    def prepare_append(self, slot: int) -> Optional[CowSplit]:
        """Make the row for the NEXT token (position ``lens[slot]``) writable:
        grow the table by a fresh block at a block boundary, copy-on-write
        split a shared boundary block.  Returns the split to device-copy, or
        None.  The caller bumps ``note_token`` after the write lands."""
        assert self.live[slot], slot
        pos = int(self.lens[slot])
        ib = pos // self.block
        assert ib < self.nb_max, "append past max_len"
        if ib == self.n_blocks[slot]:
            (bid,) = self.pool.alloc(1)
            self.tables[slot, ib] = bid
            self.n_blocks[slot] += 1
            self.pool_blocks_peak = max(self.pool_blocks_peak, self.pool.n_used)
            return None
        bid = int(self.tables[slot, ib])
        if self.pool.ref[bid] > 1:
            (fresh,) = self.pool.alloc(1)
            self.pool.release(bid)
            self.tables[slot, ib] = fresh
            return CowSplit(src=bid, dst=fresh)
        return None

    def note_token(self, slot: int) -> None:
        self.lens[slot] += 1

    def free(self, slot: int) -> None:
        """Return the slot's blocks to the pool (each freed exactly once, on
        its last reference) and zero its table AND length, so any stale
        decode write computes a row inside the dump block (table entry 0)
        without relying on out-of-range index clamping."""
        assert self.live[slot], slot
        for j in range(int(self.n_blocks[slot])):
            self.pool.release(int(self.tables[slot, j]))
        self.tables[slot, :] = 0
        self.lens[slot] = 0
        self.n_blocks[slot] = 0
        self.live[slot] = False

    def stats(self) -> dict:
        """One consolidated pool-audit snapshot (telemetry gauges read this;
        ``engine.decode_stats()`` embeds it under the paged path)."""
        return {
            "block": self.block,
            "pool_blocks": self.pool.n_blocks,
            "pool_blocks_used": self.pool.n_used,
            "pool_blocks_peak": self.pool_blocks_peak,
            "shared_block_hits": self.shared_block_hits,
            "live_slots": int(self.live.sum()),
            "live_tokens": int(self.lens[self.live].sum()),
        }

    # -- auditing (the hypothesis invariants) --------------------------- #
    def audit(self) -> None:
        """Pool-accounting invariants: ref counts == live table references,
        free list disjoint + duplicate-free, and used pool bytes == bytes of
        the live block-table entries (each distinct block counted once)."""
        refs: dict = {}
        for slot in range(self.tables.shape[0]):
            if not self.live[slot]:
                assert self.n_blocks[slot] == 0
                assert not self.tables[slot].any(), slot
                continue
            for j in range(int(self.n_blocks[slot])):
                bid = int(self.tables[slot, j])
                assert bid > 0, (slot, j)
                refs[bid] = refs.get(bid, 0) + 1
        for bid in range(1, self.pool.n_blocks):
            assert self.pool.ref[bid] == refs.get(bid, 0), bid
        free = self.pool.free_list()
        assert len(free) == len(set(free))
        assert not (set(free) & set(refs)), "freed block still referenced"
        assert self.pool.n_used == len(refs)


def block_rows(block_ids, block: int) -> np.ndarray:
    """Flat pool-row indices covered by ``block_ids`` (host-side helper for
    the engine's single-scatter landings and CoW copies)."""
    ids = np.asarray(list(block_ids), np.int64)
    return (
        ids[:, None] * block + np.arange(block, dtype=np.int64)[None, :]
    ).reshape(-1)


def init_pool_caches(
    cfg: ArchConfig, n_blocks: int, block: int = KV_BLOCK, dtype=None
) -> Any:
    """Device-side shared KV block pool: one flat-row KV buffer per layer
    kind, ``[n_periods, n_blocks * block, KV, hd]`` — the paged analogue of
    ``lm.init_state``'s slotted-dense caches."""
    from repro.models import common as common_mod
    from repro.models.blocks import BlockCache

    kinds, n_periods = _attn_kinds(cfg)
    dtype = dtype or common_mod.resolve_dtype(cfg.dtype)
    shape = (n_periods, n_blocks * block, cfg.n_kv_heads, cfg.resolved_head_dim)
    return tuple(
        BlockCache(KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)), None)
        for _ in kinds
    )


def packed_to_artifact(cfg: ArchConfig, caches: Any, seg: PackSegment, n: int) -> Any:
    """Slice one segment's first ``n`` rows out of the packed buffers as a
    standard batch-1 LMState artifact — the bridge back to ``insert_slot``
    (slot installation) and ``ContextStore.put`` (write-back)."""
    from repro.models.blocks import BlockCache

    rows = slice(seg.kv_start, seg.kv_start + n)
    return LMState(
        pos=jnp.full((1,), n, jnp.int32),
        caches=tuple(
            BlockCache(KVCache(c.attn.k[:, :, rows], c.attn.v[:, :, rows]), None)
            for c in caches
        ),
    )
