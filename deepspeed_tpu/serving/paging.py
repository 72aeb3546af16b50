"""Paged KV cache + session tiering: serve conversations, not slots.

The slot batcher pins every live conversation into one contiguous
``max_len``-row slot, so a 30-token chat strands the same HBM as a
2048-token one and concurrency is hard-capped at ``slots``.  This module
is the vLLM-style rung layered on the family ``write_slot`` /
``read_slot`` / ``reset_slot`` contract — three pieces:

- :class:`BlockAllocator` — fixed-size KV blocks (``block_tokens`` rows,
  power of two), a free-list with O(1) alloc/free, and per-block
  refcounts so block tables can *share* blocks (a pooled system prompt's
  full blocks are referenced by every conversation over it; the partial
  tail block is copied-on-write into a private block at retire).  Block 0
  is the reserved **trash block**: gather/scatter tables pad unused (and
  shared, must-not-rewrite) entries to it, so one compiled program
  handles every table.
- :class:`PagedKVPool` — the device-resident block pool.  It *is* a
  family cache with ``batch=num_blocks`` and ``max_len=block_tokens``,
  so every family (dense, MoE, int8 codes+scales) pages through the same
  generic tree ops.  Three jitted programs, registered in the batcher's
  ``CompiledProgramRegistry`` so the zero-recompile serving gate covers
  them: ``read_slot`` (slot row → batch-1 cache), ``page_gather``
  (block table → batch-1 cache), ``page_scatter`` (batch-1 cache →
  blocks).  ``row``, ``table``, and ``length`` are traced operands.
- :class:`SessionPager` + :class:`ParkStore` — session tiering.  A
  finished conversation's KV retires from its slot into pool blocks
  (warm tier); pool pressure parks the LRU session to host RAM (cold
  tier) and RAM pressure spills to disk (``park_dir``, atomic writes,
  SHA-256 verified on the way back).  A follow-up turn re-admits the
  parked KV through ``write_slot`` and prefills only the new tokens —
  instead of re-prefilling the whole conversation.  Corrupt parked bytes
  are *rejected* (checksum mismatch → drop + full re-prefill fallback),
  never decoded into a wrong answer.

Journal kinds: ``serve.page_alloc`` / ``serve.page_evict`` /
``serve.park`` / ``serve.readmit`` (plus ``serve.evict`` for TTL/LRU
drops).  Reference: ``docs/serving.md`` ("Paged KV & session tiering").
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..runtime.supervision.events import EventKind
from ..utils import fault_injection
from ..utils.lock_watch import LockName, TrackedLock
from ..utils.logging import logger

__all__ = [
    "BlockAllocator", "PagedKVPool", "ParkStore", "SessionPager",
    "PoolExhaustedError", "ParkCorruptError", "TieredSession",
]

#: the reserved trash block: never allocated, target of every padded /
#: masked table entry, content garbage by design
TRASH_BLOCK = 0


class PoolExhaustedError(RuntimeError):
    """The block pool has no free block left (after pressure eviction)."""


class ParkCorruptError(RuntimeError):
    """A parked session failed its integrity check on re-admission —
    the caller must drop it and fall back to a full re-prefill, never
    decode from corrupt KV."""


# --------------------------------------------------------------- allocator


class BlockAllocator:
    """Free-list block allocator with refcounted sharing.

    O(1) ``alloc`` (stack pop) and O(1) ``free`` (refcount decrement,
    stack push on zero).  ``share`` increments a live block's refcount —
    the copy-on-write contract: a shared block is immutable, writers
    take a fresh block and leave the shared one to its other holders;
    the last ``free`` returns it to the free list.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError(
                f"BlockAllocator needs >= 2 blocks (block 0 is the "
                f"reserved trash block), got {num_blocks}")
        self.num_blocks = int(num_blocks)
        # stack of free ids; pop()/append() keep alloc/free O(1)
        self._free = list(range(self.num_blocks - 1, 0, -1))
        self._refs = [0] * self.num_blocks
        self._refs[TRASH_BLOCK] = 1   # pinned forever

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        """Allocated (ref > 0) blocks, excluding the pinned trash block."""
        return self.num_blocks - 1 - len(self._free)

    def refs(self, bid: int) -> int:
        return self._refs[bid]

    def alloc(self) -> int:
        if not self._free:
            raise PoolExhaustedError(
                f"KV block pool exhausted: all {self.num_blocks - 1} "
                f"blocks allocated (raise serving.paging.pool_blocks or "
                f"lower park pressure)")
        bid = self._free.pop()
        self._refs[bid] = 1
        return bid

    def share(self, bid: int) -> int:
        """Add a reference to a live block (copy-on-write sharing);
        returns the block id for chaining."""
        if bid == TRASH_BLOCK or self._refs[bid] <= 0:
            raise ValueError(f"cannot share unallocated block {bid}")
        self._refs[bid] += 1
        return bid

    def free(self, bid: int) -> None:
        """Drop one reference; the block returns to the free list when
        its last holder lets go."""
        if bid == TRASH_BLOCK:
            return
        if self._refs[bid] <= 0:
            raise ValueError(f"double free of block {bid}")
        self._refs[bid] -= 1
        if self._refs[bid] == 0:
            self._free.append(bid)


def blocks_for(length: int, block_tokens: int) -> int:
    """Blocks needed to hold ``length`` tokens (ceil division)."""
    return -(-int(length) // int(block_tokens))


def pad_table(table: List[int], max_blocks: int) -> np.ndarray:
    """Fixed-shape ``[max_blocks]`` int32 table — unused entries point at
    the trash block so one compiled gather/scatter serves every table."""
    if len(table) > max_blocks:
        raise ValueError(
            f"block table of {len(table)} entries overflows the "
            f"{max_blocks}-block slot geometry")
    out = np.full((max_blocks,), TRASH_BLOCK, np.int32)
    if table:
        out[:len(table)] = np.asarray(table, np.int32)
    return out


# ------------------------------------------------------------- cache trees


def _is_bank(leaf) -> bool:
    """KV banks (k/v ``[L, B, S, H*D]`` and their scale banks ``[L, B, S,
    H]``, one layout for every model family) lead with ``[L, B, S]``; the
    ``length`` scalar is rank-0.  Nothing here reads past the third
    dimension."""
    return getattr(leaf, "ndim", 0) >= 4


def cache_bank_bytes(cache) -> int:
    """Total bytes of the cache's KV banks (host metadata only — no
    device sync)."""
    return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(cache)
               if _is_bank(leaf))


def _host_banks(cache, pad_len: int) -> List[np.ndarray]:
    """Device→host pull of a batch-1 cache's banks, trimmed to the first
    ``pad_len`` rows (a parked session pays for the blocks it uses, not
    the slot geometry)."""
    out = []
    for leaf in jax.tree_util.tree_leaves(cache):
        if _is_bank(leaf):
            arr = np.asarray(leaf)[:, :, :pad_len]
            out.append(np.ascontiguousarray(arr))
    return out


def _slot_banks(cache, row: int, length: int) -> List[np.ndarray]:
    """Device→host pull of ONE slot's banks out of a batched cache
    ``[L, B, S, ...]``, as batch-1 arrays trimmed to the first
    ``length`` rows — the export half of live session migration (the
    target rebuilds them via ``rebuild_prefix_cache``)."""
    out = []
    for leaf in jax.tree_util.tree_leaves(cache):
        if _is_bank(leaf):
            arr = np.asarray(leaf)[:, row:row + 1, :length]
            out.append(np.ascontiguousarray(arr))
    return out


def _sha_banks(arrays: List[np.ndarray], length: int) -> str:
    h = hashlib.sha256()
    h.update(str(int(length)).encode())
    for arr in arrays:
        h.update(str(arr.shape).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


# ------------------------------------------------------------------- pool


class PagedKVPool:
    """The device-resident block pool + its gather/scatter programs.

    The pool is a family cache of geometry ``[L, num_blocks,
    block_tokens, ...]`` — block *b* is row *b* — so the same tree ops
    page every cache family, int8 scale banks included.
    """

    def __init__(self, batcher, block_tokens: int, num_blocks: int):
        fam, cfg = batcher._fam, batcher._cfg
        self._fam = fam
        self._cfg = cfg
        self._kv_dtype = batcher._kv_dtype
        self.block_tokens = int(block_tokens)
        self.num_blocks = int(num_blocks)
        self.max_len = batcher.max_len
        if self.max_len % self.block_tokens:
            raise ValueError(
                f"block_tokens {self.block_tokens} must divide the "
                f"bucketed slot length {self.max_len}")
        self.max_blocks = self.max_len // self.block_tokens
        self.cache = fam.init_cache(cfg, self.num_blocks, self.block_tokens,
                                    kv_dtype=self._kv_dtype)
        self.allocator = BlockAllocator(self.num_blocks)
        #: HBM bytes of ONE block across every bank
        self.block_bytes = cache_bank_bytes(self.cache) // self.num_blocks
        #: total pool HBM footprint (allocated once, used or not)
        self.pool_bytes = cache_bank_bytes(self.cache)
        MB, bt = self.max_blocks, self.block_tokens

        def gather(pool, table, length):
            """Block table → batch-1 slot-geometry cache."""
            def g(bank):
                got = bank[:, table]                     # [L, MB, bt, ...]
                return got.reshape(bank.shape[0], 1, MB * bt,
                                   *bank.shape[3:])
            out = jax.tree_util.tree_map(
                lambda x: g(x) if _is_bank(x) else x, pool)
            return dataclasses.replace(
                out, length=jnp.asarray(length, jnp.int32))

        def scatter(pool, src, table):
            """Batch-1 slot-geometry cache → pool blocks.  Table entries
            equal to the trash block (padding, or shared/immutable blocks
            that must not be rewritten) land in block 0 and are never
            read back."""
            def s(pool_bank, src_bank):
                blocks = src_bank.reshape(src_bank.shape[0], MB, bt,
                                          *src_bank.shape[3:])
                return pool_bank.at[:, table].set(blocks)
            return jax.tree_util.tree_map(
                lambda pb, sb: s(pb, sb) if _is_bank(pb) else pb,
                pool, src)

        self._p = batcher.registry.register_all({
            "read_slot": jax.jit(
                lambda c, row, length: fam.read_slot(c, row, length)),
            "page_gather": jax.jit(gather),
            "page_scatter": jax.jit(scatter),
        })

    # ------------------------------------------------------------ programs

    def read_slot(self, slot_cache, row: int, length: int):
        return self._p["read_slot"](slot_cache, jnp.asarray(row, jnp.int32),
                                    jnp.asarray(length, jnp.int32))

    def gather(self, table: List[int], length: int):
        """Materialize a block table as a batch-1 cache (re-admission /
        park eviction read path)."""
        return self._p["page_gather"](
            self.cache, jnp.asarray(pad_table(table, self.max_blocks)),
            jnp.asarray(length, jnp.int32))

    def scatter(self, src_cache, table_for_write: np.ndarray) -> None:
        """Write a batch-1 cache's blocks into the pool.
        ``table_for_write`` is already padded/masked (immutable entries
        → trash)."""
        self.cache = self._p["page_scatter"](
            self.cache, src_cache, jnp.asarray(table_for_write))

    # --------------------------------------------------------- host bridge

    def rebuild(self, arrays: List[np.ndarray], length: int):
        """Host-parked banks (trimmed) → a batch-1 slot-geometry cache
        ready for ``write_slot``.  Rows past the parked frontier are
        zero — masked by per-row visibility and overwritten as decode
        advances, exactly like prefill-chunk padding."""
        template = self._fam.init_cache(self._cfg, 1, self.max_len,
                                        kv_dtype=self._kv_dtype)
        flat, treedef = jax.tree_util.tree_flatten(template)
        it = iter(arrays)
        out = []
        for leaf in flat:
            if _is_bank(leaf):
                src = next(it)
                full = np.zeros(leaf.shape, np.asarray(leaf).dtype)
                full[:, :, :src.shape[2]] = src
                out.append(jnp.asarray(full))
            else:
                out.append(jnp.asarray(length, jnp.int32))
        return jax.tree_util.tree_unflatten(treedef, out)


# ------------------------------------------------------------------- park


@dataclasses.dataclass
class _ParkEntry:
    tokens: np.ndarray                       # full conversation ids [T]
    length: int
    sha: str
    nbytes: int
    t_used: float
    arrays: Optional[List[np.ndarray]] = None   # ram tier
    path: Optional[str] = None                  # disk tier


class ParkStore:
    """Host-side LRU store of parked sessions: RAM first, optional disk
    spill (atomic npz + SHA-256), TTL sweep.  Dumb storage — the
    :class:`SessionPager` owns the policy decisions and journals them."""

    def __init__(self, capacity: int, park_dir: Optional[str],
                 ttl_s: float, verify: bool = True):
        self.capacity = int(capacity)
        self.park_dir = park_dir
        self.ttl_s = float(ttl_s)
        self.verify = bool(verify)
        self._entries: "OrderedDict[str, _ParkEntry]" = OrderedDict()
        if park_dir:
            os.makedirs(park_dir, exist_ok=True)

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, sid: str) -> bool:
        return sid in self._entries

    @property
    def bytes(self) -> int:
        """RAM-resident parked bytes (disk entries hold no arrays)."""
        return sum(e.nbytes for e in self._entries.values()
                   if e.arrays is not None)

    def entry(self, sid: str) -> Optional[_ParkEntry]:
        return self._entries.get(sid)

    def put(self, sid: str, tokens: np.ndarray, arrays: List[np.ndarray],
            length: int) -> List[Tuple[str, str, int]]:
        """Park a session in RAM; returns ``(sid, action, bytes)`` for
        every entry this displaced (``action`` = ``"disk"`` spill or
        ``"dropped"``)."""
        sha = _sha_banks(arrays, length)
        nbytes = sum(a.nbytes for a in arrays)
        self._entries[sid] = _ParkEntry(
            tokens=np.asarray(tokens, np.int32), length=int(length),
            sha=sha, nbytes=nbytes, t_used=time.monotonic(), arrays=arrays)
        self._entries.move_to_end(sid)
        displaced: List[Tuple[str, str, int]] = []
        # capacity bounds RAM entries; disk entries are payload-free here.
        # Other entries demote LRU-first; with capacity 0 the entry just
        # parked spills straight through to disk (or is dropped).
        while self._ram_count() > self.capacity:
            victim = self._lru_ram(exclude=sid)
            if victim is None:
                victim = sid if self._entries[sid].arrays is not None \
                    else None
            if victim is None:
                break
            displaced.append(self._demote(victim))
        return displaced

    def _ram_count(self) -> int:
        return sum(1 for e in self._entries.values()
                   if e.arrays is not None)

    def _lru_ram(self, exclude: str) -> Optional[str]:
        for k, e in self._entries.items():
            if e.arrays is not None and k != exclude:
                return k
        return None

    def _demote(self, sid: str) -> Tuple[str, str, int]:
        """Spill a RAM entry to disk (atomic) or drop it entirely."""
        e = self._entries[sid]
        freed = e.nbytes
        if self.park_dir:
            from ..runtime.checkpoint_engine.storage import atomic_write_npz
            path = os.path.join(
                self.park_dir,
                hashlib.sha256(sid.encode()).hexdigest()[:24] + ".npz")
            arrays = {f"bank{i}": a for i, a in enumerate(e.arrays)}
            arrays["tokens"] = e.tokens
            arrays["meta"] = np.asarray([e.length], np.int64)
            arrays["sha"] = np.frombuffer(
                bytes.fromhex(e.sha), np.uint8).copy()
            atomic_write_npz(path, arrays)
            e.path, e.arrays = path, None
            return sid, "disk", freed
        del self._entries[sid]
        return sid, "dropped", freed

    def load(self, sid: str) -> Tuple[List[np.ndarray], int]:
        """Return ``(banks, length)`` for a parked session, verifying the
        SHA-256 taken at park time.  Raises :class:`ParkCorruptError` on
        any mismatch/damage — the caller falls back to re-prefill."""
        e = self._entries[sid]
        if e.arrays is not None:
            arrays, length = e.arrays, e.length
        else:
            try:
                with np.load(e.path) as z:
                    n = len([k for k in z.files if k.startswith("bank")])
                    arrays = [z[f"bank{i}"] for i in range(n)]
                    length = int(z["meta"][0])
            except Exception as exc:
                raise ParkCorruptError(
                    f"parked session {sid!r} unreadable at {e.path}: "
                    f"{exc}") from exc
        if self.verify and _sha_banks(arrays, length) != e.sha:
            raise ParkCorruptError(
                f"parked session {sid!r} failed its integrity check "
                f"(tier={'ram' if e.arrays is not None else 'disk'}) — "
                "rejecting the KV and re-prefilling")
        e.t_used = time.monotonic()
        self._entries.move_to_end(sid)
        return arrays, length

    def touch(self, sid: str) -> None:
        e = self._entries.get(sid)
        if e is not None:
            e.t_used = time.monotonic()
            self._entries.move_to_end(sid)

    def drop(self, sid: str) -> int:
        """Remove an entry (and its disk file); returns bytes freed."""
        e = self._entries.pop(sid, None)
        if e is None:
            return 0
        if e.path:
            try:
                os.remove(e.path)
            except OSError as exc:
                logger.warning(f"[serving] parked file cleanup failed: {exc}")
        return e.nbytes

    def sweep(self, now: float) -> List[Tuple[str, int, float]]:
        """Drop entries idle past the TTL; returns
        ``(sid, bytes, idle_s)`` per drop."""
        stale = [(k, now - e.t_used) for k, e in self._entries.items()
                 if now - e.t_used > self.ttl_s]
        out = []
        for sid, idle in stale:
            out.append((sid, self.drop(sid), idle))
        return out


# ------------------------------------------------------------------ pager


@dataclasses.dataclass
class TieredSession:
    """One retained conversation: where its KV lives and how to get it
    back."""

    sid: str
    tokens: np.ndarray          # full conversation ids [T] (the match key)
    length: int
    tier: str                   # "pool" | "ram" | "disk"
    table: Optional[List[int]]  # pool tier: owned/shared block ids
    immutable_upto: int         # leading blocks that must never be
    # rewritten (shared prefix blocks, or blocks already scattered whose
    # content cannot change — the scatter table points them at trash)
    nbytes: int
    t_used: float


@dataclasses.dataclass
class _RowLedger:
    """Block accounting for a session actively decoding in a slot."""

    sid: str
    table: List[int]
    immutable_upto: int
    poolable: bool = True


@dataclasses.dataclass
class ReadmitResult:
    cache: Any                  # batch-1 cache ready to extend/write_slot
    reused: int                 # tokens restored (no re-prefill for these)
    tier: str                   # "pool" | "ram" | "disk"
    table: List[int]            # block table the row ledger inherits
    immutable_upto: int


class SessionPager:
    """Policy half of the tiering subsystem: owns the pool, the park
    store, the per-session records, and the per-row ledgers.  All
    mutation happens on the gateway's scheduler thread; ``stats()`` is
    safe from any thread (lock-guarded counters)."""

    def __init__(self, batcher, config, emit: Optional[Callable] = None,
                 metrics=None):
        bt = min(int(config.block_tokens), batcher.max_len)
        pool_blocks = config.pool_blocks
        if pool_blocks is None:
            pool_blocks = batcher.slots * (batcher.max_len // bt)
        # +1: block 0 is the reserved trash block
        self.pool = PagedKVPool(batcher, bt, pool_blocks + 1)
        self.park = ParkStore(config.park_capacity, config.park_dir,
                              config.park_ttl_s, verify=config.park_verify)
        self._batcher = batcher
        self._emit = emit if emit is not None else (lambda *a, **k: None)
        self._metrics = metrics
        self._lock = TrackedLock(LockName.SERVE_PAGER)
        self.sessions: "OrderedDict[str, TieredSession]" = OrderedDict()
        self.rows: Dict[int, _RowLedger] = {}
        self.slot_bytes = cache_bank_bytes(batcher.cache)
        #: HBM census watermark (bytes); None = exhaustion-driven only
        self.hbm_high_watermark = config.hbm_high_watermark
        self._last_census_t = 0.0

    # ---------------------------------------------------------- accounting

    def _count(self, field: str, n: int = 1) -> None:
        if self._metrics is not None:
            self._metrics.count(field, n)

    @property
    def block_tokens(self) -> int:
        return self.pool.block_tokens

    def conversations(self) -> int:
        """Concurrently-held conversations: decoding rows plus every
        session retained in a warm/cold tier."""
        with self._lock:
            return len(self.rows) + len(self.sessions)

    def hbm_bytes(self) -> int:
        """Serving HBM footprint: the slot cache plus the whole pool
        (allocated once, used or not — honest accounting)."""
        return self.slot_bytes + self.pool.pool_bytes

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            tiers = {"pool": 0, "ram": 0, "disk": 0}
            for s in self.sessions.values():
                tiers[s.tier] += 1
            return {
                "pool_blocks_total": self.pool.num_blocks - 1,
                "pool_blocks_used": self.pool.allocator.used_blocks,
                "pool_bytes": self.pool.pool_bytes,
                "block_bytes": self.pool.block_bytes,
                "park_bytes": self.park.bytes,
                "sessions_pool": tiers["pool"],
                "sessions_ram": tiers["ram"],
                "sessions_disk": tiers["disk"],
                "decoding_sessions": len(self.rows),
            }

    # ----------------------------------------------------------- admission

    def readmit(self, sid: str, tokens: np.ndarray) -> Optional[ReadmitResult]:
        """Try to restore a session's KV for a follow-up turn.  ``None``
        means no usable tier copy (never seen, token mismatch, no new
        tokens, corrupt, or faulted) — the caller re-prefills; a corrupt
        or faulted copy is dropped so it can never be served."""
        fault_injection.fire("serve.readmit", session=sid)
        with self._lock:
            sess = self.sessions.get(sid)
        if sess is None:
            return None
        tokens = np.asarray(tokens, np.int32)
        if tokens.shape[0] <= sess.length or \
                not np.array_equal(tokens[:sess.length], sess.tokens):
            # a follow-up must extend the stored conversation; anything
            # else is a different conversation wearing the same id
            return None
        if sess.tier == "pool":
            cache = self.pool.gather(sess.table, sess.length)
            with self._lock:
                self.sessions.pop(sid, None)
            return ReadmitResult(
                cache=cache, reused=sess.length, tier="pool",
                table=list(sess.table),
                immutable_upto=sess.length // self.block_tokens)
        try:
            arrays, length = self.park.load(sid)
        except ParkCorruptError as exc:
            logger.warning(f"[serving] {exc}")
            self.drop_session(sid, reason="corrupt")
            return None
        cache = self.pool.rebuild(arrays, length)
        tier = sess.tier
        self.park.drop(sid)   # bytes move from park back to the slot
        with self._lock:
            self.sessions.pop(sid, None)
        return ReadmitResult(cache=cache, reused=length, tier=tier,
                             table=[], immutable_upto=0)

    def begin_row(self, row: int, sid: str, start_len: int,
                  table: Optional[List[int]] = None,
                  immutable_upto: int = 0) -> None:
        """Start block accounting for a session decoding in ``row``.
        ``table``/``immutable_upto`` carry over a re-admitted pool table
        or shared prefix blocks (already ref-counted by the caller)."""
        led = _RowLedger(sid=sid, table=list(table or []),
                         immutable_upto=int(immutable_upto))
        self._grow(led, start_len)
        self.rows[row] = led

    def share_prefix(self, prefix_table: List[int],
                     prefix_len: int) -> Tuple[List[int], int]:
        """Reference a pooled prefix's *full* blocks for a new session
        table (copy-on-write: the partial tail block is NOT shared — the
        session writes its own copy of that range at retire)."""
        full = prefix_len // self.block_tokens
        shared = [self.pool.allocator.share(b) for b in prefix_table[:full]]
        return shared, full

    def on_tick(self, row: int, length: int) -> None:
        """Decode-tick frontier accounting: crossing into a new block
        allocates it (pressure-evicting parked-LRU pool sessions); true
        exhaustion marks the row unpoolable — it parks straight to host
        at retire instead of wedging the tick loop."""
        led = self.rows.get(row)
        if led is not None and led.poolable:
            self._grow(led, length)

    def _grow(self, led: _RowLedger, length: int) -> None:
        needed = blocks_for(length, self.block_tokens)
        while led.poolable and len(led.table) < needed:
            bid = self._alloc_with_pressure()
            if bid is None:
                led.poolable = False
                self._emit(EventKind.SERVE_PAGE_EVICT, session=led.sid,
                           blocks=0, bytes=0, reason="exhausted")
                break
            led.table.append(bid)
            self._count("pages_allocated")

    def _alloc_with_pressure(self) -> Optional[int]:
        alloc = self.pool.allocator
        while True:
            try:
                return alloc.alloc()
            except PoolExhaustedError:
                if not self._evict_pool_lru():
                    return None

    def _evict_pool_lru(self, reason: str = "pressure",
                        **fields: Any) -> bool:
        """Park the least-recently-used pool-tier session to host RAM;
        returns False when nothing is evictable."""
        with self._lock:
            victim = next((s for s in self.sessions.values()
                           if s.tier == "pool"), None)
        if victim is None:
            return False
        cache = self.pool.gather(victim.table, victim.length)
        self._emit(EventKind.SERVE_PAGE_EVICT, session=victim.sid,
                   blocks=len(victim.table),
                   bytes=len(victim.table) * self.pool.block_bytes,
                   reason=reason, **fields)
        self._count("pool_evictions")
        # drop the pool-tier record and free its blocks FIRST —
        # _park_arrays re-inserts the session under its host tier
        with self._lock:
            self.sessions.pop(victim.sid, None)
        for bid in victim.table:
            self.pool.allocator.free(bid)
        try:
            self._park_arrays(victim.sid, victim.tokens, cache,
                              victim.length)
        except (OSError, RuntimeError, ValueError) as exc:
            logger.warning(
                f"[serving] parking evicted session {victim.sid!r} "
                f"failed ({exc}); dropping it — next turn re-prefills")
            self._emit(EventKind.SERVE_EVICT, prefix=None,
                       session=victim.sid, reason="park_failed",
                       idle_s=round(time.monotonic() - victim.t_used, 3),
                       bytes=victim.nbytes)
        return True

    # -------------------------------------------------------------- retire

    def retire(self, row: int, tokens: np.ndarray) -> None:
        """A session's conversation finished in ``row``: keep its KV for
        the follow-up turn.  Poolable rows scatter into their block
        table (warm tier); unpoolable ones park straight to host."""
        led = self.rows.pop(row, None)
        if led is None:
            return
        tokens = np.asarray(tokens, np.int32)
        length = int(tokens.shape[0])
        sid = led.sid
        if sid in self.sessions:       # superseded by a concurrent turn
            self.drop_session(sid, reason="superseded")
        if led.poolable and len(led.table) >= blocks_for(
                length, self.block_tokens):
            # scatter only the mutable tail: immutable (shared prefix /
            # already-correct re-admitted) blocks point at trash
            write = pad_table(led.table, self.pool.max_blocks)
            write[:led.immutable_upto] = TRASH_BLOCK
            src = self.pool.read_slot(self._batcher.cache, row, length)
            self.pool.scatter(src, write)
            with self._lock:
                # blocks fully covered by the scattered length are now
                # immutable pool content (readmit recomputes this floor;
                # a partial tail block is rescattered next retire)
                self.sessions[sid] = TieredSession(
                    sid=sid, tokens=tokens, length=length, tier="pool",
                    table=led.table,
                    immutable_upto=length // self.block_tokens,
                    nbytes=len(led.table) * self.pool.block_bytes,
                    t_used=time.monotonic())
            self._emit(EventKind.SERVE_PAGE_ALLOC, session=sid,
                       blocks=len(led.table),
                       free_blocks=self.pool.allocator.free_blocks)
            return
        # unpoolable: park directly from the slot
        cache = self.pool.read_slot(self._batcher.cache, row, length)
        for bid in led.table:
            self.pool.allocator.free(bid)
        try:
            self._park_arrays(sid, tokens, cache, length)
        except (OSError, RuntimeError, ValueError) as exc:
            logger.warning(
                f"[serving] parking session {sid!r} failed ({exc}); "
                "dropping it — next turn re-prefills")

    def _park_arrays(self, sid: str, tokens: np.ndarray, cache,
                     length: int) -> None:
        """Pull a batch-1 cache to host and park it (RAM, spilling LRU
        to disk per capacity).  The ``serve.park`` fault point models a
        failing host/disk park."""
        fault_injection.fire("serve.park", session=sid)
        self._batcher.registry.note_host_sync("serve.park")
        pad_len = blocks_for(length, self.block_tokens) * self.block_tokens
        arrays = _host_banks(cache, pad_len)
        displaced = self.park.put(sid, tokens, arrays, length)
        nbytes = sum(a.nbytes for a in arrays)
        with self._lock:
            self.sessions[sid] = TieredSession(
                sid=sid, tokens=np.asarray(tokens, np.int32),
                length=int(length), tier="ram", table=None,
                immutable_upto=0, nbytes=nbytes, t_used=time.monotonic())
        self._emit(EventKind.SERVE_PARK, session=sid, tokens=int(length),
                   blocks=blocks_for(length, self.block_tokens),
                   bytes=nbytes, tier="ram")
        self._count("parked")
        for vid, action, vbytes in displaced:
            if action == "disk":
                with self._lock:
                    if vid in self.sessions:
                        self.sessions[vid].tier = "disk"
                self._emit(EventKind.SERVE_PARK, session=vid,
                           tokens=int(self.sessions[vid].length
                                      if vid in self.sessions else 0),
                           blocks=0, bytes=vbytes, tier="disk")
                self._count("park_spills")
            else:
                with self._lock:
                    self.sessions.pop(vid, None)
                self._emit(EventKind.SERVE_EVICT, prefix=None, session=vid,
                           reason="park_capacity", idle_s=None,
                           bytes=vbytes)

    def row_released(self, row: int) -> None:
        """A slot freed without a retire (cancel/timeout/failure/shutdown):
        drop the ledger and its block references."""
        led = self.rows.pop(row, None)
        if led is None:
            return
        for bid in led.table:
            self.pool.allocator.free(bid)

    def drop_session(self, sid: str, reason: str) -> None:
        with self._lock:
            sess = self.sessions.pop(sid, None)
        if sess is None:
            return
        freed = self.park.drop(sid)
        if sess.table:
            for bid in sess.table:
                self.pool.allocator.free(bid)
        self._emit(EventKind.SERVE_EVICT, prefix=None, session=sid,
                   reason=reason,
                   idle_s=round(time.monotonic() - sess.t_used, 3),
                   bytes=sess.nbytes if sess.tier == "pool" else freed)

    # ---------------------------------------------------------- prefix ops

    def pool_prefix(self, cache, length: int) -> Optional[List[int]]:
        """Scatter a freshly-built batch-1 prefix cache into pool blocks;
        returns the table, or ``None`` on exhaustion (the caller keeps
        the plain cache entry instead)."""
        table: List[int] = []
        for _ in range(blocks_for(length, self.block_tokens)):
            bid = self._alloc_with_pressure()
            if bid is None:
                for b in table:
                    self.pool.allocator.free(b)
                return None
            table.append(bid)
            self._count("pages_allocated")
        self.pool.scatter(cache, pad_table(table, self.pool.max_blocks))
        return table

    def gather_prefix(self, table: List[int], length: int):
        return self.pool.gather(table, length)

    def free_table(self, table: List[int]) -> int:
        """Release a block table (prefix eviction); refcounted — blocks
        still shared by live sessions survive.  Returns bytes whose last
        reference this released."""
        freed = 0
        for bid in table:
            last = self.pool.allocator.refs(bid) == 1
            self.pool.allocator.free(bid)
            if last:
                freed += self.pool.block_bytes
        return freed

    # ----------------------------------------------------------- housekeep

    def pressure_sweep(self, now: Optional[float] = None,
                       live_bytes: Optional[int] = None,
                       min_interval_s: float = 1.0,
                       max_evictions: int = 4) -> int:
        """HBM-census-driven eviction (``serving.paging.hbm_high_watermark``):
        when the telemetry live-buffer census exceeds the watermark, park
        pool-LRU sessions to host — bounded per sweep so one census spike
        cannot wedge the scheduler loop — journaling ``serve.page_evict``
        with the observed pressure.  The census walk is rate-limited
        (``min_interval_s``); ``live_bytes`` overrides it for tests.
        Returns the number of sessions evicted."""
        wm = self.hbm_high_watermark
        if wm is None:
            return 0
        now = time.monotonic() if now is None else now
        if live_bytes is None:
            if now - self._last_census_t < min_interval_s:
                return 0
            self._last_census_t = now
            from ..telemetry.metrics import live_buffer_bytes
            live_bytes = live_buffer_bytes()
        if live_bytes <= wm:
            return 0
        evicted = 0
        while evicted < max_evictions and self._evict_pool_lru(
                reason="hbm_pressure", pressure=int(live_bytes),
                watermark=int(wm)):
            evicted += 1
        return evicted

    def sweep(self, now: Optional[float] = None) -> None:
        """TTL sweep of the park store — runs from the scheduler tick
        path, so an idle gateway still releases host memory; the HBM
        pressure sweep (census vs ``hbm_high_watermark``) rides the same
        cadence."""
        now = time.monotonic() if now is None else now
        self.pressure_sweep(now)
        for sid, nbytes, idle in self.park.sweep(now):
            with self._lock:
                self.sessions.pop(sid, None)
            self._emit(EventKind.SERVE_EVICT, prefix=None, session=sid,
                       reason="ttl", idle_s=round(idle, 3), bytes=nbytes)
