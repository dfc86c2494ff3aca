"""Paged columnar memory for the ingest buffer (ROADMAP #3).

One grow-array triple per block window would double as it grows (up to
2x overshoot per window), free nothing at a window drop until the arrays
die, and make `drop_window_prefix` COPY the whole surviving suffix under
the shard lock at every flush.  Following PAPERS.md "Ragged Paged
Attention" (fixed pages, ragged index vectors), the buffer's window logs
draw from a shared pool of FIXED-SIZE columnar pages:

- ``PagePool`` hands out pages cut from arena slabs (slabs are never
  resized, so page views stay stable); freed pages go to a free list
  and are reused before the arena grows; a free list deeper than
  ``max_free_pages`` releases whole all-free slabs back to the OS —
  counted as evictions on the saturation plane.
- ``PagedColumnLog`` is a window's append log over a page list +
  a head offset: appends fill the tail page, bulk appends fill pages
  slab-assign by slab-assign, and ``drop_prefix`` just advances the
  head and frees fully-covered pages — O(pages freed), no copy under
  the shard lock.

Saturation-plane discipline (m3lint ``inv-pagepool-gauge``): every
``PagePool(...)`` construction site must call ``monitor_pool`` in the
same scope — pools feed the aggregate ``queue_*{queue=page_pool}``
gauges refreshed by the PR-11 snapshot hook, so occupancy and eviction
are dashboards, not mysteries.
"""

from __future__ import annotations

import threading
import weakref

import numpy as np

from m3_tpu.utils.instrument import monitor_queue, register_snapshot_hook

PAGE_ROWS = 1024          # rows per page (sidx i32 + times i64 + vbits u64)
_SLAB_PAGES = 64          # pages allocated per arena slab
_BYTES_PER_ROW = 4 + 8 + 8


class _Slab:
    __slots__ = ("sidx", "times", "vbits", "free_count")

    def __init__(self) -> None:
        n = _SLAB_PAGES * PAGE_ROWS
        self.sidx = np.empty(n, np.int32)
        self.times = np.empty(n, np.int64)
        self.vbits = np.empty(n, np.uint64)
        self.free_count = 0  # pages of this slab currently on the free list


class PagePool:
    """Fixed-size columnar page allocator shared by one shard's window
    logs.  Thread safety: allocation/free take the pool's own lock (the
    shard buffer lock already serializes its callers; the pool lock
    keeps the pool safe for any future cross-window sharing and for the
    snapshot hook reading occupancy from scrape threads)."""

    def __init__(self, max_free_pages: int = 4 * _SLAB_PAGES):
        self._lock = threading.Lock()
        self._slabs: dict[int, _Slab] = {}
        self._next_slab = 0
        self._free: list[int] = []
        self.max_free_pages = max_free_pages
        self.pages_in_use = 0
        self.evicted_pages = 0  # pages released back to the OS

    # page id encodes (slab, page-within-slab)

    def alloc(self) -> int:
        with self._lock:
            if self._free:
                pid = self._free.pop()
                self._slabs[pid // _SLAB_PAGES].free_count -= 1
            else:
                sid = self._next_slab
                self._next_slab += 1
                slab = self._slabs[sid] = _Slab()
                base = sid * _SLAB_PAGES
                self._free.extend(range(base + _SLAB_PAGES - 1, base, -1))
                slab.free_count = _SLAB_PAGES - 1
                pid = base
            self.pages_in_use += 1
            return pid

    def free(self, pages: list[int]) -> None:
        if not pages:
            return
        with self._lock:
            for pid in pages:
                self._free.append(pid)
                self._slabs[pid // _SLAB_PAGES].free_count += 1
            self.pages_in_use -= len(pages)
            if len(self._free) > self.max_free_pages:
                self._evict_locked()

    def _evict_locked(self) -> None:
        """Release whole all-free slabs until the free list is back under
        bound (arena shrink — the pool's eviction story; in-use pages are
        never touched)."""
        doomed = [sid for sid, slab in self._slabs.items()
                  if slab.free_count == _SLAB_PAGES]
        for sid in doomed:
            if len(self._free) <= self.max_free_pages:
                break
            base = sid * _SLAB_PAGES
            self._free = [p for p in self._free
                          if not base <= p < base + _SLAB_PAGES]
            del self._slabs[sid]
            self.evicted_pages += _SLAB_PAGES

    def columns(self, pid: int):
        """(sidx, times, vbits) views of one page — stable for the page's
        lifetime (slabs never resize)."""
        slab = self._slabs[pid // _SLAB_PAGES]
        off = (pid % _SLAB_PAGES) * PAGE_ROWS
        end = off + PAGE_ROWS
        return (slab.sidx[off:end], slab.times[off:end],
                slab.vbits[off:end])

    @property
    def total_pages(self) -> int:
        return len(self._slabs) * _SLAB_PAGES

    @property
    def resident_bytes(self) -> int:
        return self.total_pages * PAGE_ROWS * _BYTES_PER_ROW


class PagedColumnLog:
    """Growable (series_idx, time, value_bits) append log over pool
    pages: logical row i lives at physical offset head+i of the page
    list."""

    __slots__ = ("pool", "pages", "head", "n", "_view_cache")

    def __init__(self, pool: PagePool) -> None:
        self.pool = pool
        self.pages: list[int] = []
        self.head = 0  # physical offset of logical row 0 in pages[0]
        self.n = 0
        self._view_cache = None  # (n, head, sidx, times, vbits)

    def _phys_end(self) -> int:
        return self.head + self.n

    def append(self, sidx: int, t_ns: int, vbits: int) -> None:
        end = self._phys_end()
        if end == len(self.pages) * PAGE_ROWS:
            self.pages.append(self.pool.alloc())
        ps, pt, pv = self.pool.columns(self.pages[end // PAGE_ROWS])
        off = end % PAGE_ROWS
        ps[off] = sidx
        pt[off] = t_ns
        pv[off] = vbits
        self.n += 1

    def extend(self, sidx: np.ndarray, t_ns: np.ndarray,
               vbits: np.ndarray) -> None:
        """Bulk append filling pages slab-assign by slab-assign; row
        order is preserved, so seal-time last-write-wins resolves
        batched and per-point writes identically."""
        m = len(sidx)
        end = self._phys_end()
        need_pages = -(-(end + m) // PAGE_ROWS)
        while len(self.pages) < need_pages:
            self.pages.append(self.pool.alloc())
        done = 0
        while done < m:
            pos = end + done
            pid = self.pages[pos // PAGE_ROWS]
            off = pos % PAGE_ROWS
            take = min(PAGE_ROWS - off, m - done)
            ps, pt, pv = self.pool.columns(pid)
            ps[off:off + take] = sidx[done:done + take]
            pt[off:off + take] = t_ns[done:done + take]
            pv[off:off + take] = vbits[done:done + take]
            done += take
        self.n += m

    def view(self):
        """Contiguous (sidx, times, vbits) copies of the logical rows.
        Cached by (n, head): steady-state reads between writes pay the
        materialization once; any append or prefix drop invalidates by
        construction (n/head change)."""
        cached = self._view_cache
        if cached is not None and cached[0] == self.n \
                and cached[1] == self.head:
            return cached[2], cached[3], cached[4]
        sidx = np.empty(self.n, np.int32)
        times = np.empty(self.n, np.int64)
        vbits = np.empty(self.n, np.uint64)
        done = 0
        while done < self.n:
            pos = self.head + done
            ps, pt, pv = self.pool.columns(self.pages[pos // PAGE_ROWS])
            off = pos % PAGE_ROWS
            take = min(PAGE_ROWS - off, self.n - done)
            sidx[done:done + take] = ps[off:off + take]
            times[done:done + take] = pt[off:off + take]
            vbits[done:done + take] = pv[off:off + take]
            done += take
        self._view_cache = (self.n, self.head, sidx, times, vbits)
        return sidx, times, vbits

    def drop_prefix(self, k: int) -> None:
        """Drop the first k logical rows by advancing the head and
        freeing fully-covered pages — O(pages freed), no suffix
        copy under the shard lock."""
        k = min(k, self.n)
        self.head += k
        self.n -= k
        # (n, head) is NOT unique over the log's lifetime once a prefix
        # drop has run (a refill can land on a previously-cached pair
        # and serve pre-flush rows — lost-write class): invalidate
        self._view_cache = None
        full = self.head // PAGE_ROWS
        if full:
            self.pool.free(self.pages[:full])
            del self.pages[:full]
            self.head -= full * PAGE_ROWS
        if self.n == 0 and self.pages:
            self.pool.free(self.pages)
            self.pages = []
            self.head = 0

    def release(self) -> None:
        """Return every page to the pool (window drop/expiry)."""
        if self.pages:
            self.pool.free(self.pages)
            self.pages = []
        self.head = 0
        self.n = 0
        self._view_cache = None

    def fill_ratio(self) -> float:
        cap = len(self.pages) * PAGE_ROWS
        return (self.head + self.n) / cap if cap else 1.0


# ---------------------------------------------------------------------------
# saturation-plane registration (PR-11 snapshot-hook seam)
# ---------------------------------------------------------------------------

_pools_lock = threading.Lock()
_pools: "weakref.WeakSet[PagePool]" = weakref.WeakSet()


def monitor_pool(pool: PagePool) -> PagePool:
    """Register a pool with the aggregate saturation gauges.  Every
    ``PagePool(...)`` construction site must call this in the same
    scope (m3lint ``inv-pagepool-gauge``) — the aggregate keeps the
    gauge label set bounded while per-shard pools come and go."""
    with _pools_lock:
        _pools.add(pool)
    return pool


def _aggregate():
    used = total = evicted = bytes_ = 0
    with _pools_lock:
        pools = list(_pools)
    for p in pools:
        used += p.pages_in_use
        total += p.total_pages
        evicted += p.evicted_pages
        bytes_ += p.resident_bytes
    return used, total, evicted, bytes_


# ONE module-level registration covers every pool (depth = pages in use,
# capacity = pages resident, drops = pages evicted back to the OS); the
# byte figure rides a gauge from the snapshot hook below. The monitor
# refresh evaluates depth_fn FIRST (instrument._refresh_queue_monitors),
# so depth computes the aggregate once per snapshot and the other two
# callables read the memo instead of re-walking every pool.
_agg_memo = [(0, 0, 0, 0)]


def _agg_fresh() -> int:
    _agg_memo[0] = _aggregate()
    return _agg_memo[0][0]


monitor_queue("page_pool", _agg_fresh,
              capacity=lambda: _agg_memo[0][1],
              drops_fn=lambda: _agg_memo[0][2])


def _snapshot_hook(registry) -> None:
    # fresh walk (the monitor memo only refreshes for the default
    # registry's snapshots)
    _used, _total, _evicted, nbytes = _aggregate()
    registry.root_scope("storage").subscope("page_pool").gauge(
        "resident_bytes", float(nbytes))


register_snapshot_hook(_snapshot_hook)
