"""Paged KV / SSM state pool: page table, prefix cache, preemption.

The engine's sequence state used to be one dense ``max_ctx`` cache slab
pinned per slot for the request's whole lifetime.  This module replaces
that with a **block/paged pool** (the vLLM PagedAttention idea, adapted
to the hybrid attn/SSM stacks this repo serves):

* **Attention KV** (for latent attention: the latent and the shared
  rotary key, ``models.mla.LatentCache``) lives in fixed-size physical pages
  (``page_size`` tokens each, ``num_pages`` total per engine) shared by
  every serving slot.  One host-side page table (``(max_batch, NP)``
  int32, ``NP = ceil(max_ctx / page_size)``) maps logical to physical
  pages — a *single* table serves every layer because page allocation
  advances in lockstep across layers.  The table is pushed to the
  device once per engine iteration and enters the jitted mega-steps as
  a traced argument, so allocation churn never retraces.
* **Mamba2 conv/ssm state** is O(1) per slot, so it stays dense per
  row; the pool snapshots it *by value* at chunk boundaries
  (``models.mamba2.ssm_state_slice`` — plain slices, so snapshot ->
  restore is bit-exact).

On top of the pool sit two behaviors:

* **Prefix caching** — after each prefill chunk the engine registers
  the slot's state under a content hash of the prompt-prefix *chain*
  (``h_i = sha256(h_{i-1} || token_i)``, keyed at every chunk
  boundary).  A later request that shares a cached prefix admits with
  the prefix's pages attached (full pages shared by refcount, the
  partial tail page copied — copy-on-write, since decode will write
  into it) and the SSM snapshot restored; only the unshared suffix is
  computed.  Per-token outputs are chunk-partition-invariant under
  ``drop_free`` (PR 5's batching-invariance property), so cache-hit
  runs stay bit-identical to cold sequential runs.
* **Preemption** — a request whose sub-layer progress is at an
  iteration boundary can be evicted to a :class:`PreemptedState`
  handle: the page-table row detaches in O(1) (no data movement — page
  refs transfer to the handle) and the SSM rows snapshot by value.
  Restoring into any free slot re-attaches the pages and writes the
  snapshot back — bit-identical resumption, asserted by tests.

Page lifecycle is refcounted: a page is freed only when no slot row,
prefix-cache entry, or preemption handle references it.  Prefix entries
are evicted LRU — on explicit pressure (``max_prefix_entries``) and on
demand when the free list runs dry; :class:`PoolExhausted` is raised
only when eviction cannot recover enough pages (active slots + handles
hold everything).

See docs/statepool.md for the design discussion and the accounting
fields surfaced in ``Engine.stats``.
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import mamba2 as ssm_mod
from repro.models.attention import KVCache
from repro.models.mla import LatentCache

# per-token state held in pool pages: attention K/V, or the latent and
# shared rotary key of latent attention
PAGED = (KVCache, LatentCache)


class PoolExhausted(RuntimeError):
    """No free pages left after evicting every evictable prefix entry."""


def hash_chain(tokens) -> List[bytes]:
    """Content-hash chain over a token sequence.

    ``out[i]`` identifies the prefix ``tokens[:i+1]`` — equal prefixes
    give equal keys regardless of which request produced them, and the
    chain construction makes every key depend on the full prefix, not
    just its last chunk."""
    h = hashlib.sha256()
    out: List[bytes] = []
    for t in tokens:
        h.update(int(t).to_bytes(8, "little", signed=True))
        out.append(h.digest())
    return out


@dataclass
class PrefixEntry:
    """One cached prompt prefix: shared full pages + owned tail copy."""
    key: bytes
    length: int                      # tokens covered
    page_ids: List[int]              # ceil(length / page_size) refcounted ids
    ssm: tuple = ()                  # per-layer SSMState snapshots (or ())
    hits: int = 0


@dataclass
class PreemptedState:
    """Everything needed to resume an evicted request bit-identically."""
    request: object                  # engine RequestState (progress == 0)
    page_ids: List[int]              # ownership transferred from the slot row
    cache_len: int
    ssm: tuple = ()


class StatePool:
    """Host-side metadata manager for the paged serving state.

    Owns the free list, refcounts, per-slot page lists, the page table,
    and the prefix-cache LRU.  Device arrays are owned by the engine;
    methods that need data movement (partial-page copy-on-write, SSM
    snapshot/restore) return instructions or take/yield snapshots, and
    the engine applies them with the module-level array helpers below.
    """

    def __init__(self, *, max_batch: int, max_ctx: int, page_size: int,
                 num_pages: Optional[int] = None,
                 max_prefix_entries: int = 64,
                 bytes_per_page: int = 0, ssm_bytes_per_row: int = 0):
        assert page_size >= 1 and max_ctx >= 1
        self.page_size = page_size
        self.pages_per_slot = -(-max_ctx // page_size)
        # default headroom: every slot full twice over — half live, half
        # available to prefix entries / preemption handles
        self.num_pages = (num_pages if num_pages is not None
                          else 2 * max_batch * self.pages_per_slot)
        if self.num_pages < max_batch * self.pages_per_slot:
            raise ValueError(
                f"state pool too small: {self.num_pages} pages < "
                f"{max_batch} slots x {self.pages_per_slot} pages/slot — "
                f"active slots alone could exhaust it")
        self.max_prefix_entries = max_prefix_entries
        self.bytes_per_page = bytes_per_page
        self.ssm_bytes_per_row = ssm_bytes_per_row
        self.table = np.zeros((max_batch, self.pages_per_slot), np.int32)
        self.free: Deque[int] = deque(range(self.num_pages))
        self.ref = np.zeros((self.num_pages,), np.int64)
        self.slot_pages: List[List[int]] = [[] for _ in range(max_batch)]
        self.entries: "OrderedDict[bytes, PrefixEntry]" = OrderedDict()
        self._ssm_rows_held = 0          # snapshots held by entries+handles
        self.stats: Dict[str, int] = {
            "pool_pages": self.num_pages,
            "pool_pages_in_use": 0, "pool_peak_pages": 0,
            "resident_state_bytes": 0, "peak_resident_state_bytes": 0,
            "cache_hits": 0, "cache_misses": 0, "cache_evictions": 0,
            "prefill_tokens_saved": 0,
        }

    # ------------------------------------------------------------------
    # page bookkeeping
    # ------------------------------------------------------------------

    def pages_in_use(self) -> int:
        return self.num_pages - len(self.free)

    def _account(self) -> None:
        used = self.pages_in_use()
        self.stats["pool_pages_in_use"] = used
        self.stats["pool_peak_pages"] = max(self.stats["pool_peak_pages"],
                                            used)
        resident = (used * self.bytes_per_page
                    + self._ssm_rows_held * self.ssm_bytes_per_row)
        self.stats["resident_state_bytes"] = resident
        self.stats["peak_resident_state_bytes"] = max(
            self.stats["peak_resident_state_bytes"], resident)

    def _alloc(self, n: int) -> List[int]:
        while len(self.free) < n and self.entries:
            self._evict_lru()
        if len(self.free) < n:
            raise PoolExhausted(
                f"state pool exhausted: need {n} pages, "
                f"{len(self.free)} free of {self.num_pages} "
                f"(active slots and preemption handles hold the rest)")
        ids = [self.free.popleft() for _ in range(n)]
        for pid in ids:
            self.ref[pid] = 1
        self._account()
        return ids

    def _addref(self, pid: int) -> None:
        self.ref[pid] += 1

    def _deref(self, pid: int) -> None:
        self.ref[pid] -= 1
        assert self.ref[pid] >= 0, f"page {pid} refcount underflow"
        if self.ref[pid] == 0:
            self.free.append(pid)

    # ------------------------------------------------------------------
    # slot lifecycle
    # ------------------------------------------------------------------

    def ensure(self, slot: int, length: int) -> None:
        """Grow ``slot``'s page run to cover ``length`` tokens.

        Called on the host *before* each engine iteration (the table
        must be read-only inside the jitted step), so capacity exists
        for every KV write the coming iteration performs."""
        need = -(-length // self.page_size)
        have = len(self.slot_pages[slot])
        if need <= have:
            return
        ids = self._alloc(need - have)
        self.table[slot, have:need] = ids
        self.slot_pages[slot].extend(ids)

    def release_slot(self, slot: int) -> None:
        """Drop the slot row's references (request finished/cancelled).
        Pages shared with prefix entries survive via their refcounts."""
        for pid in self.slot_pages[slot]:
            self._deref(pid)
        self.slot_pages[slot] = []
        self._account()

    # ------------------------------------------------------------------
    # prefix cache
    # ------------------------------------------------------------------

    def lookup_prefix(self, keys: List[bytes],
                      max_len: int) -> Optional[PrefixEntry]:
        """Longest cached prefix of a prompt, capped at ``max_len``
        tokens (the engine passes ``len(prompt) - 1`` — at least one
        prompt token must run so first-token logits exist)."""
        best: Optional[PrefixEntry] = None
        for L in range(min(max_len, len(keys)), 0, -1):
            e = self.entries.get(keys[L - 1])
            if e is not None and e.length == L:
                best = e
                break
        if best is not None:
            self.entries.move_to_end(best.key)
            best.hits += 1
        return best

    def register_prefix(self, key: bytes, length: int, slot: int,
                        ssm: tuple = ()) -> Optional[Tuple[int, int]]:
        """Register the first ``length`` cached tokens of ``slot``.

        Full pages are shared by reference; a partial tail page needs a
        copy (decode will keep writing into the slot's own tail), so
        the pool allocates a destination and returns ``(src, dst)`` for
        the engine to copy on-device (:func:`copy_page`).  Returns None
        when nothing needs copying or the key is already cached."""
        if key in self.entries:
            self.entries.move_to_end(key)
            return None
        n_full, tail = divmod(length, self.page_size)
        row = self.slot_pages[slot]
        assert len(row) >= n_full + (1 if tail else 0), \
            f"slot {slot} holds {len(row)} pages, prefix needs {length} tokens"
        ids = list(row[:n_full])
        for pid in ids:
            self._addref(pid)
        copy = None
        if tail:
            dst = self._alloc(1)[0]
            ids.append(dst)
            copy = (row[n_full], dst)
        self.entries[key] = PrefixEntry(key=key, length=length,
                                        page_ids=ids, ssm=ssm)
        if ssm != ():
            self._ssm_rows_held += 1
        while len(self.entries) > self.max_prefix_entries:
            self._evict_lru()
        self._account()
        return copy

    def attach_prefix(self, entry: PrefixEntry,
                      slot: int) -> Optional[Tuple[int, int]]:
        """Point ``slot``'s table row at a cached prefix.

        Full pages are shared (refcount+1) — safe because the slot only
        ever writes at positions >= entry.length, which land beyond
        them.  A partial tail page is copied into a fresh page the slot
        owns (returned as ``(src, dst)`` for the engine to copy)."""
        assert not self.slot_pages[slot], \
            f"attach_prefix into non-empty slot {slot}"
        n_full, tail = divmod(entry.length, self.page_size)
        ids = list(entry.page_ids[:n_full])
        for pid in ids:
            self._addref(pid)
        copy = None
        if tail:
            dst = self._alloc(1)[0]
            copy = (entry.page_ids[n_full], dst)
            ids.append(dst)
        self.table[slot, :len(ids)] = ids
        self.slot_pages[slot] = ids
        self.stats["cache_hits"] += 1
        self.stats["prefill_tokens_saved"] += entry.length
        self._account()
        return copy

    def _evict_lru(self) -> None:
        key, entry = self.entries.popitem(last=False)
        for pid in entry.page_ids:
            self._deref(pid)
        if entry.ssm != ():
            self._ssm_rows_held -= 1
        self.stats["cache_evictions"] += 1
        self._account()

    # ------------------------------------------------------------------
    # preemption
    # ------------------------------------------------------------------

    def detach_slot(self, slot: int, *, has_ssm: bool = False) -> List[int]:
        """Transfer the slot row's page ownership to a preemption handle
        (no refcount change — the handle now holds the row's refs).

        ``has_ssm`` marks a handle that actually snapshots SSM state
        (hybrid/Mamba2 models): only those hold an off-slot SSM row.
        A pure-attention preemption must not inflate the SSM-row
        accounting (and with it ``resident_state_bytes``)."""
        ids = self.slot_pages[slot]
        self.slot_pages[slot] = []
        if has_ssm:
            self._ssm_rows_held += 1
        self._account()
        return ids

    def attach_pages(self, slot: int, page_ids: List[int], *,
                     has_ssm: bool = False) -> None:
        """Re-attach a preemption handle's pages to a (fresh) slot row.
        ``has_ssm`` as in :meth:`detach_slot` — releases the handle's
        SSM row only if the handle held one."""
        assert not self.slot_pages[slot], \
            f"attach_pages into non-empty slot {slot}"
        if len(page_ids) > self.pages_per_slot:
            raise ValueError(f"{len(page_ids)} pages exceed the "
                             f"{self.pages_per_slot}-page slot row")
        self.table[slot, :len(page_ids)] = page_ids
        self.slot_pages[slot] = list(page_ids)
        if has_ssm:
            self._ssm_rows_held -= 1
        self._account()

    def drop_handle(self, handle: PreemptedState) -> None:
        """Discard a preemption handle (requeue-mode: state is thrown
        away and the request restarts from its prompt)."""
        for pid in handle.page_ids:
            self._deref(pid)
        if handle.ssm != ():
            self._ssm_rows_held -= 1
        self._account()


# ---------------------------------------------------------------------------
# device-array helpers (applied by the engine; the pool stays host-only)
# ---------------------------------------------------------------------------


def _is_ssm(part) -> bool:
    return isinstance(part, ssm_mod.SSMState)


def copy_page(caches, src: int, dst: int):
    """Copy one physical page across every attention layer (the
    copy-on-write step for partial tail pages)."""
    out = []
    for c in caches:
        if isinstance(c.kv, PAGED):
            kv = jax.tree.map(lambda a: a.at[:, dst].set(a[:, src]), c.kv)
            out.append(type(c)(kv, c.ssm))
        else:
            out.append(c)
    return tuple(out)


def snapshot_ssm(caches, row: int) -> tuple:
    """Value snapshot of one slot's SSM state across every SSM layer
    (``()`` placeholders for attention layers)."""
    return tuple(ssm_mod.ssm_state_slice(c.ssm, row) if _is_ssm(c.ssm)
                 else () for c in caches)


def restore_ssm(caches, snap: tuple, row: int):
    """Write a :func:`snapshot_ssm` back into ``row``."""
    out = []
    for c, s in zip(caches, snap):
        if _is_ssm(c.ssm):
            out.append(type(c)(c.kv, ssm_mod.ssm_state_restore(c.ssm, s, row)))
        else:
            out.append(c)
    return tuple(out)


def zero_ssm(caches, row: int):
    """Reset one slot's SSM rows to the initial state (fresh admission
    into a recycled slot must not inherit the previous occupant's
    recurrent state)."""
    out = []
    for c in caches:
        if _is_ssm(c.ssm):
            out.append(type(c)(c.kv, ssm_mod.ssm_state_zero_row(c.ssm, row)))
        else:
            out.append(c)
    return tuple(out)


def has_ssm(caches) -> bool:
    return any(_is_ssm(c.ssm) for c in caches)


def has_latent(caches) -> bool:
    return any(isinstance(c.kv, LatentCache) for c in caches)


def merge_prefill(caches, dense_caches, page_ids: List[int], slot: int,
                  page_size: int):
    """Scatter a one-shot (batch=1) dense prefill into the pool.

    ``dense_caches`` come from ``api.prefill_fn`` — KV (n_periods, 1,
    max_ctx, n_kv, hd), SSM (n_periods, 1, ...).  KV reshapes into the
    ``len(page_ids)`` pages the slot owns; SSM rows write at ``slot``."""
    ids = jnp.asarray(page_ids, jnp.int32)
    n = len(page_ids)
    out = []
    for c, d in zip(caches, dense_caches):
        if isinstance(c.kv, PAGED):
            def put(pages, dense):
                arr = dense[:, 0]                      # (n_periods, S, ...)
                need = n * page_size
                S = arr.shape[1]
                if need > S:
                    pad = [(0, 0)] * arr.ndim
                    pad[1] = (0, need - S)
                    arr = jnp.pad(arr, pad)
                chunk = arr[:, :need].reshape(
                    arr.shape[0], n, page_size, *arr.shape[2:])
                return pages.at[:, ids].set(chunk.astype(pages.dtype))
            kv = jax.tree.map(put, c.kv, d.kv)
            out.append(type(c)(kv, c.ssm))
        elif _is_ssm(c.ssm):
            st = jax.tree.map(
                lambda big, small: big.at[:, slot].set(
                    small[:, 0].astype(big.dtype)), c.ssm, d.ssm)
            out.append(type(c)(c.kv, st))
        else:
            out.append(c)
    return tuple(out)


def state_bytes(caches) -> Tuple[int, int]:
    """(bytes per physical page across all attn / mla layers, SSM bytes per
    slot row across all SSM layers) — the pool's accounting constants."""
    page_b = 0
    ssm_b = 0
    for c in caches:
        if isinstance(c.kv, PAGED):
            for a in c.kv:
                # (n_periods, P, page_size, n_kv, hd): per page = all but P
                page_b += int(a.shape[0] * np.prod(a.shape[2:])) * a.dtype.itemsize
        if _is_ssm(c.ssm):
            for a in c.ssm:
                # (n_periods, B, ...): per row = all but B
                ssm_b += int(a.shape[0] * np.prod(a.shape[2:])) * a.dtype.itemsize
    return page_b, ssm_b
