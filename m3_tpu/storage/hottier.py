"""Device-resident hot tier: a bounded, paged cache of PREPARED query
slabs pinned in device memory (ROADMAP #3).

The index match, the batched read and its CSR assembly, the
whole-query compiler's host prep (window bounds, per-device slab fill,
prefix sums) plus the host->device transfer of those slabs are what a
REPEATED dashboard query pays after the block cache has already
amortized the decode.  This tier keys the prepared slab set on the
fetch's content identity — (the namespaces' data versions over the
fetch's range, selector, time range, eval grid, plan base, precision)
— all of it known BEFORE storage is read, so the compiler probes the
tier first
(`compiler._run_plan`) and an unchanged repeat skips the index match
(`query_ids`), the read (`read_many`), `window_bounds_batch`,
`_slab_cuts`/`_fill_slabs` and the transfer entirely: the compiled
program re-runs against warm device buffers.  For that an entry keeps
on the host, beside its device slabs, the little the compiler took
from the fetch itself: the series and sample counts (padding ledger),
the group labels of an aggregating plan or the series' label dicts of
one without (counted into the entry's `nbytes`), and the series and
datapoint counts the query limits were charged, which a hit charges
again — a repeat over a limit is refused as its first run was.  The
version in the key is that of the blocks the fetch's range touches
(`Namespace.data_version_in`): a write, flush, repair or bootstrap
bumps the version of the block it changed, expiry and a placement
change bump every range's, so a warm entry is never served over
changed data, and the head block's writes leave an entry over sealed
history warm; the version is sampled before the read, so a racing
write can only make an entry stale.

On CPU backends the "device" is jax's host platform and the tier is an
ordinary arena of committed buffers; on a TPU the same code pins the
working set in device HBM (the serving-tier story).  A
``bf16`` mirror (half the bytes; EQuARX's reduced-precision argument)
is negotiated PER QUERY: the API layer's ``?precision=bf16`` opt-in
installs a thread-local grant, and only plan bases whose output
tolerance permits it (`compiler._BF16_OK_BASES`) quantize — the
precision rides the cache key, so full-precision queries can never read
a quantized entry.

Saturation plane: byte occupancy/entries/evictions ride the
``queue_*{queue=hot_tier}`` gauges (PR-11 snapshot-hook seam, m3lint
``inv-pagepool-gauge``); per-plan ``hit``/``miss`` counters (one lookup
a plan) and ``fetch_skipped`` (plans served without a fetch) land under
``storage.hot_tier``, and the ``hot_tier`` block on ``?explain=analyze``.
``M3_TPU_HOT_TIER_MB=0`` disables the tier.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from contextlib import contextmanager

from m3_tpu.utils import compute_stats
from m3_tpu.utils.instrument import monitor_queue


class HotTier:
    """Bytes-bounded LRU of prepared slab entries (device arrays)."""

    def __init__(self, max_bytes: int):
        self.max_bytes = max_bytes
        self._lock = threading.Lock()
        # key -> (entry, nbytes, devices its slabs sit on)
        self._entries: OrderedDict = OrderedDict()
        self.bytes_used = 0
        # resident bytes of the reduced-precision mirror alone (entries
        # prepared under a bf16 grant) — the device-memory gauges split
        # it out so operators can see what the opt-in actually saves
        self.bytes_bf16 = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, key):
        with self._lock:
            hit = self._entries.get(key)
            if hit is None:
                self.misses += 1
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            return hit[0]

    @staticmethod
    def _is_bf16(entry) -> bool:
        try:
            return entry.get("precision") == "bf16"
        except AttributeError:
            return False

    @staticmethod
    def _device_width(entry) -> int:
        """How many devices the entry's slabs actually sit on (observed
        from the arrays' shardings, not from the mesh that was asked)."""
        try:
            arrays = entry.values()
        except AttributeError:
            return 0
        return len({d for a in arrays if hasattr(a, "sharding")
                    for d in a.sharding.device_set})

    def put(self, key, entry: dict, nbytes: int) -> None:
        if nbytes > self.max_bytes:
            return  # one oversized query must not wipe the working set
        width = self._device_width(entry)  # fixed once the slabs are put
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self.bytes_used -= old[1]
                if self._is_bf16(old[0]):
                    self.bytes_bf16 -= old[1]
            self._entries[key] = (entry, nbytes, width)
            self.bytes_used += nbytes
            if self._is_bf16(entry):
                self.bytes_bf16 += nbytes
            while self.bytes_used > self.max_bytes and self._entries:
                _k, (e, nb, _w) = self._entries.popitem(last=False)
                self.bytes_used -= nb
                if self._is_bf16(e):
                    self.bytes_bf16 -= nb
                self.evictions += 1

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self.bytes_used = 0
            self.bytes_bf16 = 0

    def stats(self) -> dict:
        """Entries + device bytes (total and bf16-mirror share) for the
        compute_stats device-cache gauges and /debug/compute."""
        with self._lock:
            # `devices`: how many devices the widest resident entry's
            # slabs sit on (a mesh that leaves every slab on its first
            # device shows 1)
            return {"entries": len(self._entries),
                    "devices": max((w for _e, _nb, w
                                    in self._entries.values()), default=0),
                    "bytes": self.bytes_used,
                    "bf16_bytes": self.bytes_bf16,
                    "evictions": self.evictions,
                    "hits": self.hits, "misses": self.misses}

    def __len__(self) -> int:
        return len(self._entries)


_lock = threading.Lock()
_default: HotTier | None = None
_default_built = False


def default() -> HotTier | None:
    """The process hot tier, sized by M3_TPU_HOT_TIER_MB (default 256;
    0 disables). Built lazily on the first compiled query; the built
    flag is read lock-free on the hot path (set LAST, after _default,
    so a racing reader sees either "not built" or the finished tier)."""
    global _default, _default_built
    if _default_built:
        return _default
    with _lock:
        if not _default_built:
            try:
                mb = int(os.environ.get("M3_TPU_HOT_TIER_MB", "256"))
            except ValueError:
                mb = 256
            _default = HotTier(mb << 20) if mb > 0 else None
            _default_built = True
        return _default


def reset_default() -> None:
    """Drop the process tier (tests re-read the env on next use)."""
    global _default, _default_built
    with _lock:
        _default = None
        _default_built = False


# saturation-plane registration: depth/capacity in BYTES, drops =
# LRU evictions (one module-level registration, label set bounded)
monitor_queue("hot_tier",
              lambda: _default.bytes_used if _default is not None else 0,
              capacity=lambda: _default.max_bytes
              if _default is not None else 0,
              drops_fn=lambda: _default.evictions
              if _default is not None else 0)

# device-cache ledger registration: entries + device bytes (incl. the
# bf16-mirror share) ride the compute.device_cache{cache=hot_tier}
# gauges and the /debug/compute payload (utils/compute_stats reads,
# never imports storage)
compute_stats.register_device_cache(
    "hot_tier",
    lambda: _default.stats() if _default is not None
    else {"entries": 0, "bytes": 0, "bf16_bytes": 0})


# ---------------------------------------------------------------------------
# per-query precision negotiation (the bf16 mirror opt-in)
# ---------------------------------------------------------------------------

_tl = threading.local()


@contextmanager
def negotiated_precision(precision: str | None):
    """Install the query's precision grant for this thread ("bf16" from
    the API layer's ?precision=bf16; None = full precision). The
    compiler honors it only for tolerance-permitting plan bases."""
    prev = getattr(_tl, "precision", None)
    _tl.precision = precision
    try:
        yield
    finally:
        _tl.precision = prev


def query_precision() -> str | None:
    return getattr(_tl, "precision", None)
