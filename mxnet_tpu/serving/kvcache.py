"""Paged KV cache: the pooled page allocator behind continuous batching.

The decode engine's working memory is K/V history, and its lifetime is
per-REQUEST, not per-batch: requests of wildly different lengths join
and leave the running batch every step. Contiguous per-slot buffers
sized for the worst case waste HBM proportional to (max_len − actual);
this module instead pools fixed-size pages (``page_size`` tokens each,
shared across layers in one allocation) and hands each request exactly
``ceil(tokens / page_size)`` of them — the vLLM-style discipline, on the
same accounting substrate as the rest of the framework:

- **Shape-stable programs.** The compiled decode step reads K/V through
  a (slots, max_pages) int32 page table (gather) and writes through
  scatter indices, so which physical pages a request holds never
  changes the program. Page 0 is the reserved NULL page: page-table
  padding and inactive-slot writes all target it, making masked slots
  harmless without a branch.
- **One accounting path.** The page arrays are NDArray handles
  registered in the :class:`~mxnet_tpu.telemetry.memory.BufferCensus`
  ``kvcache`` pool; :meth:`PagedKVCache.total_bytes` prices them with
  the same ``device_bytes()`` rule the census uses, so allocator bytes
  == census bytes by construction (a tier-1 test pins the equality).
  ``MXNET_MEMORY_BUDGET`` therefore covers the cache like any other
  pool, and an OOM rides the PR 7 post-mortem dump with the pages
  attributed.
- **Admission = free pages.** :meth:`can_reserve` / :meth:`reserve` are
  the decode engine's admission-control primitive: a request that
  cannot get its pages up front is shed with a typed
  ``Overloaded(reason="kvcache")`` instead of corrupting a neighbour
  mid-flight.

Donation discipline: the engine's compiled step donates the page
arrays and rebinds each handle's ``_data`` after dispatch — the census
weakrefs survive because the HANDLE survives (telemetry/memory.py's
registration contract).

**Prefix sharing + copy-on-write** (docs/SERVING.md "Speculative decode
& prefix sharing"): the allocator additionally keeps a content-hashed
registry over committed prefill pages. Because a page's K/V content is
a function of the ENTIRE token prefix up to its end (the recurrent
state threads through every position), the registry key is the full
token prefix ``prompt[:pos]`` — hashed for lookup, and byte-verified
against the stored tokens before any sharing decision (a hash
collision must never alias two different prefixes). A request whose
prompt extends a registered prefix maps the same physical pages
(:meth:`PagedKVCache.share` bumps per-page refcounts) and the engine
skips prefilling the shared region. Pages are freed refcount-exactly:
:meth:`release` returns a page to the free list only when its LAST
holder leaves, and evicts any registry entry built over it — the
registry pins nothing by itself, so allocator bytes == census bytes
keeps holding and a shed/EOS frees exactly the private tail. A write
landing on a page held by >= 2 requests first gets a private copy
(:meth:`cow` — one device-side page copy, no host sync), so divergence
after a shared prefix can never corrupt a neighbour.
"""
from __future__ import annotations

import hashlib
from typing import Dict, List, Optional, Tuple

import numpy as onp

import jax.numpy as jnp

from ..base import MXNetError
from ..ndarray.ndarray import NDArray

__all__ = ["PagedKVCache", "KV_PAGE_SIZE", "pages_needed",
           "prefix_hash"]

#: tokens per KV page — the shipped default behind
#: ``MXNET_DECODE_KV_PAGE_SIZE`` (consumers read the live value through
#: ``serving.decode.kv_page_size()``, never this constant directly)
KV_PAGE_SIZE = 16


def pages_needed(tokens: int, page_size: int) -> int:
    """Pages covering ``tokens`` positions."""
    return max(1, -(-int(tokens) // max(1, int(page_size))))


def prefix_hash(tokens) -> int:
    """Registry key for a committed token prefix: a stable content hash
    over the int32 token bytes. Lookups ALWAYS byte-verify against the
    stored tokens afterwards — tests monkeypatch this to a constant to
    pin that a hash collision alone can never alias two prefixes."""
    b = onp.ascontiguousarray(tokens, onp.int32).tobytes()
    return int.from_bytes(hashlib.blake2b(b, digest_size=8).digest(),
                          "little")


class _PrefixEntry:
    """One registered prefix: ``pages`` hold the K/V of
    ``tokens[:pos]`` (last page possibly partial), ``state`` is the
    engine's opaque recurrent-state snapshot at ``pos``."""

    __slots__ = ("tokens", "pages", "pos", "state")

    def __init__(self, tokens, pages, pos, state):
        self.tokens = onp.ascontiguousarray(tokens, onp.int32)
        self.pages = tuple(int(p) for p in pages)
        self.pos = int(pos)
        self.state = state


class PagedKVCache:
    """Fixed-size K/V pages for ``num_layers`` attention layers plus a
    free-list allocator over them.

    Layout: one K array and one V array of shape
    ``(num_layers, num_pages, page_size, num_heads, head_dim)`` — a
    single allocation each, so the census sees two buffers, not 2·L·P.
    Page ids are shared across layers (a request's page p holds its
    tokens ``[p*page_size, (p+1)*page_size)`` in EVERY layer), which
    keeps the page table one (slots, max_pages) array.

    Page 0 is reserved as the null page and never allocated.
    """

    def __init__(self, num_layers: int, num_heads: int, head_dim: int,
                 num_pages: int, page_size: Optional[int] = None,
                 dtype: str = "float32"):
        if page_size is None:
            from . import decode as _dec
            page_size = _dec.kv_page_size()
        if num_pages < 2:
            raise MXNetError(
                f"PagedKVCache needs num_pages >= 2 (page 0 is the "
                f"reserved null page), got {num_pages}")
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.num_pages = int(num_pages)
        self.page_size = max(1, int(page_size))
        self.dtype = str(dtype)
        shape = (self.num_layers, self.num_pages, self.page_size,
                 self.num_heads, self.head_dim)
        # NDArray handles: _data rebinds after every donated step while
        # the handle (and its census registration) survives
        self.k_pages = NDArray(jnp.zeros(shape, dtype=self.dtype))
        self.v_pages = NDArray(jnp.zeros(shape, dtype=self.dtype))
        self._free: List[int] = list(range(self.num_pages - 1, 0, -1))
        self._owned: Dict[object, List[int]] = {}
        self._reserved: Dict[object, int] = {}
        # prefix sharing: per-page holder counts (only pages held by
        # >= 2 owners appear), the content-hash registry, and the
        # page -> registry-keys index driving refcount-exact eviction
        self._refcnt: Dict[int, int] = {}
        self._prefix: Dict[int, List[_PrefixEntry]] = {}
        self._page_keys: Dict[int, set] = {}
        self.cow_copies = 0
        self.prefix_hits = 0
        from .. import telemetry as _t
        _t.memory.census().register("kvcache", self.k_pages)
        _t.memory.census().register("kvcache", self.v_pages)
        self._g_pages = _t.registry().gauge(_t.names.DECODE_KV_PAGES,
                                            label_key="state")
        self._m_prefix_hits = _t.registry().counter(
            _t.names.DECODE_PREFIX_HITS)
        self._m_cow = _t.registry().counter(_t.names.DECODE_COW_COPIES)
        self._publish()

    # ---------------- accounting ----------------
    @property
    def bytes_per_page(self) -> int:
        """Bytes one page costs across K+V and every layer (itemsize ·
        page_size · heads · head_dim · layers · 2)."""
        itemsize = 2 if self.dtype == "bfloat16" \
            else onp.dtype(self.dtype).itemsize
        return (2 * self.num_layers * self.page_size * self.num_heads
                * self.head_dim * itemsize)

    def total_bytes(self) -> int:
        """Allocator-side bytes of the page arrays — priced with the
        census's ``device_bytes`` rule so the two accountings cannot
        drift (one accounting path; tier-1 pins the equality)."""
        from ..telemetry.memory import device_bytes
        return device_bytes(self.k_pages) + device_bytes(self.v_pages)

    def free_pages(self) -> int:
        """Allocatable pages right now (reservations excluded)."""
        return len(self._free) - sum(self._reserved.values())

    def used_pages(self) -> int:
        """PHYSICAL pages allocated (a page shared by N requests
        counts once — that is the whole point of sharing)."""
        return self.num_pages - 1 - len(self._free)

    def logical_pages(self) -> int:
        """Request-side page holdings summed over owners (a shared
        page counts once PER holder); logical - used = pages saved by
        prefix sharing."""
        return sum(len(p) for p in self._owned.values())

    def shared_pages(self) -> int:
        """Physical pages currently mapped by >= 2 owners."""
        return sum(1 for n in self._refcnt.values() if n >= 2)

    def utilization(self) -> float:
        """used / allocatable (the null page is outside both)."""
        cap = self.num_pages - 1
        return self.used_pages() / cap if cap else 0.0

    # ---------------- admission ----------------
    def can_reserve(self, n: int) -> bool:
        return self.free_pages() >= int(n)

    def reserve(self, owner, n: int) -> bool:
        """Earmark ``n`` pages for ``owner`` (admission control):
        reserved pages are excluded from :meth:`free_pages` so two
        admitted requests can never race for the same page. Returns
        False (nothing reserved) when the pool cannot cover it."""
        n = int(n)
        if not self.can_reserve(n):
            return False
        self._reserved[owner] = self._reserved.get(owner, 0) + n
        self._publish()
        return True

    def unreserve(self, owner):
        self._reserved.pop(owner, None)
        self._publish()

    def trim_reservation(self, owner, keep: int):
        """Lower ``owner``'s reservation to at most ``keep`` pages —
        the seat-time correction when a prefix-cache hit means the
        submit-time worst-case pricing over-reserved."""
        keep = max(0, int(keep))
        have = self._reserved.get(owner, 0)
        if have > keep:
            if keep:
                self._reserved[owner] = keep
            else:
                self._reserved.pop(owner, None)
            self._publish()

    # ---------------- alloc / free ----------------
    def alloc(self, owner, n: int = 1) -> Optional[List[int]]:
        """Allocate ``n`` pages to ``owner``, drawing down its
        reservation first. None when the free list cannot cover it
        (an admitted request never sees this if it reserved honestly)."""
        n = int(n)
        reserved = self._reserved.get(owner, 0)
        unreserved_need = max(0, n - reserved)
        if unreserved_need > self.free_pages():
            return None
        if reserved:
            left = max(0, reserved - n)
            if left:
                self._reserved[owner] = left
            else:
                self._reserved.pop(owner, None)
        pages = [self._free.pop() for _ in range(n)]
        self._owned.setdefault(owner, []).extend(pages)
        self._publish()
        return pages

    def pages_of(self, owner) -> List[int]:
        return list(self._owned.get(owner, ()))

    def release(self, owner):
        """Return every page ``owner`` holds (and any leftover
        reservation) to the free list — the slot-retire path. A SHARED
        page only leaves ``owner``'s holdings: it goes back to the
        free list (and its registry entries are evicted) exactly when
        the last holder releases it — refcount-exact frees, so a
        mid-stream shed or EOS returns precisely the private tail."""
        pages = self._owned.pop(owner, [])
        freed = []
        for p in reversed(pages):
            n = self._refcnt.get(p)
            if n is not None and n >= 2:
                if n == 2:
                    self._refcnt.pop(p, None)
                else:
                    self._refcnt[p] = n - 1
                continue
            self._refcnt.pop(p, None)
            self._evict_prefixes(p)
            self._free.append(p)
            freed.append(p)
        self._reserved.pop(owner, None)
        self._publish()
        return len(freed)

    # ---------------- prefix sharing + copy-on-write ----------------
    def page_shared(self, page: int) -> bool:
        """Whether a write to ``page`` needs a private copy first."""
        return self._refcnt.get(int(page), 1) >= 2

    def share(self, owner, pages) -> List[int]:
        """Map already-allocated ``pages`` into ``owner``'s holdings
        (the prefix-cache hit path): each page's holder count bumps and
        the page now frees only when its LAST holder releases."""
        pages = [int(p) for p in pages]
        for p in pages:
            if not 1 <= p < self.num_pages or p in self._free:
                raise MXNetError(f"share: page {p} is not allocated")
            self._refcnt[p] = self._refcnt.get(p, 1) + 1
        self._owned.setdefault(owner, []).extend(pages)
        self.prefix_hits += 1
        try:
            self._m_prefix_hits.inc()
        except Exception:    # pragma: no cover - telemetry never fatal
            pass
        self._publish()
        return pages

    def cow(self, owner, page: int) -> int:
        """Copy-on-write: give ``owner`` a private copy of ``page``
        before it writes (one device-side page copy across K, V and
        every layer — async, no host sync). Draws the copy target from
        ``owner``'s reservation/free list, swaps it into the holdings,
        and drops ``owner``'s hold on the original. Returns the new
        page id."""
        page = int(page)
        held = self._owned.get(owner, [])
        if page not in held:
            raise MXNetError(f"cow: owner does not hold page {page}")
        got = self.alloc(owner, 1)
        if got is None:
            raise MXNetError(
                "cow: no page available for a copy-on-write target "
                "(admission under-priced the unshared tail)")
        new = got[0]
        kd, vd = self.k_pages._data, self.v_pages._data
        self.k_pages._data = kd.at[:, new].set(kd[:, page])
        self.v_pages._data = vd.at[:, new].set(vd[:, page])
        held.remove(page)
        n = self._refcnt.get(page)
        if n is not None:
            if n <= 2:
                self._refcnt.pop(page, None)
            else:
                self._refcnt[page] = n - 1
        self.cow_copies += 1
        try:
            self._m_cow.inc()
        except Exception:    # pragma: no cover - telemetry never fatal
            pass
        self._publish()
        return new

    def register_prefix(self, tokens, pos: int, pages, state=None):
        """Commit ``tokens[:pos]`` -> ``pages`` into the content-hash
        registry (``state`` = the engine's recurrent-state snapshot at
        ``pos``). Entries hold no refcount of their own: they are
        evicted the moment any underlying page is freed."""
        pos = int(pos)
        if pos < 1:
            return
        toks = onp.ascontiguousarray(
            onp.asarray(tokens, onp.int32).ravel()[:pos])
        key = prefix_hash(toks)
        bucket = self._prefix.setdefault(key, [])
        for e in bucket:
            if e.pos == pos and onp.array_equal(e.tokens, toks):
                return                      # already registered
        entry = _PrefixEntry(toks, pages, pos, state)
        bucket.append(entry)
        for p in entry.pages:
            self._page_keys.setdefault(p, set()).add(key)

    def lookup_prefix(self, prompt, max_pos: Optional[int] = None):
        """Longest registered prefix of ``prompt`` (hash lookup per
        registered boundary position, then a BYTE compare against the
        stored tokens — a hash collision must never share). Returns the
        :class:`_PrefixEntry` or None; ``max_pos`` caps the usable
        prefix length (the engine keeps >= 1 prompt token to prefill)."""
        prompt = onp.asarray(prompt, onp.int32).ravel()
        cap = prompt.size if max_pos is None else min(int(max_pos),
                                                      prompt.size)
        positions = sorted({e.pos for b in self._prefix.values()
                            for e in b if e.pos <= cap}, reverse=True)
        for pos in positions:
            key = prefix_hash(onp.ascontiguousarray(prompt[:pos]))
            for e in self._prefix.get(key, ()):
                if e.pos == pos and onp.array_equal(
                        e.tokens, prompt[:pos]):
                    return e
        return None

    def prefix_entries(self) -> int:
        return sum(len(b) for b in self._prefix.values())

    def _evict_prefixes(self, page: int):
        """Drop every registry entry built over ``page`` (called when
        the page returns to the free list)."""
        for key in self._page_keys.pop(page, ()):
            bucket = self._prefix.get(key)
            if not bucket:
                continue
            bucket[:] = [e for e in bucket if page not in e.pages]
            if not bucket:
                self._prefix.pop(key, None)

    # ---------------- observability ----------------
    def _publish(self):
        try:
            self._g_pages.set(self.used_pages(), label="used")
            self._g_pages.set(self.free_pages(), label="free")
            self._g_pages.set(self.shared_pages(), label="shared")
        except Exception:    # pragma: no cover - telemetry never fatal
            pass

    def stats(self) -> dict:
        return {
            "num_pages": self.num_pages,
            "page_size": self.page_size,
            "used_pages": self.used_pages(),
            "logical_pages": self.logical_pages(),
            "shared_pages": self.shared_pages(),
            "free_pages": self.free_pages(),
            "reserved_pages": sum(self._reserved.values()),
            "owners": len(self._owned),
            "prefix_entries": self.prefix_entries(),
            "prefix_hits": self.prefix_hits,
            "cow_copies": self.cow_copies,
            "bytes_per_page": self.bytes_per_page,
            "total_bytes": self.total_bytes(),
            "utilization": round(self.utilization(), 4),
        }

    def __repr__(self):
        s = self.stats()
        return (f"PagedKVCache(pages={s['used_pages']}/"
                f"{self.num_pages - 1} used, page_size={self.page_size}, "
                f"layers={self.num_layers}, "
                f"bytes={s['total_bytes']})")
