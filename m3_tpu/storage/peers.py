"""Peer block access + peers bootstrap + replica repair.

Role parity with the reference's peers bootstrapper
(/root/reference/src/dbnode/storage/bootstrap/bootstrapper/peers — new
nodes stream blocks from replicas) and the background repairer
(storage/repair.go:839-1011 — compare per-series block checksums across
replicas, stream + merge differing blocks). A peer is anything exposing
block metadata and stream reads: an in-process Database (integration
harness) or a NodeAPI HTTP client; the same divergence math runs
device-resident for device-held blocks via parallel.collectives.
"""

from __future__ import annotations

import base64
import json
import threading
import urllib.request
import zlib
from dataclasses import dataclass
from typing import Protocol

import numpy as np

from m3_tpu.client.breaker import BreakerConfig, HostPolicy
from m3_tpu.storage.buffer import merge_dedup
from m3_tpu.storage.fileset import FilesetWriter
from m3_tpu.utils import faults


class PeerSource(Protocol):
    def block_metadata(self, namespace: str, shard: int, block_start: int
                       ) -> dict[bytes, dict]: ...

    def stream_block(self, namespace: str, shard: int, block_start: int,
                     series_id: bytes) -> tuple[bytes, bytes]: ...

    def block_starts(self, namespace: str, shard: int) -> list[int]: ...

    def rollup_digests(self, namespace: str, shard: int
                       ) -> dict[int, tuple[int, int]]: ...


# -- rollup digest wire format ---------------------------------------------
#
# The repair plane's steady-state traffic is "are we in sync?" — one row
# per flushed block, exchanged every cycle by every replica pair. That
# must not be per-series float64 JSON (ROADMAP #5(c), EQuARX discipline:
# comparison traffic wants the leanest encoding that answers the
# question), so the whole shard's digest table rides as ONE packed
# little-endian array: (block_start i64, digest u64, n_series u32) per
# block — 20 bytes per block vs ~60 bytes of JSON object keys alone.

ROLLUP_DTYPE = np.dtype([("block_start", "<i8"), ("digest", "<u8"),
                         ("n_series", "<u4")])


def pack_rollup(digests: dict[int, tuple[int, int]]) -> bytes:
    """{block_start: (digest, n_series)} -> packed ROLLUP_DTYPE bytes,
    rows sorted by block_start (deterministic wire bytes)."""
    arr = np.empty(len(digests), ROLLUP_DTYPE)
    for i, bs in enumerate(sorted(digests)):
        digest, n_series = digests[bs]
        arr[i] = (bs, digest, n_series)
    return arr.tobytes()


def unpack_rollup(raw: bytes) -> dict[int, tuple[int, int]]:
    if len(raw) % ROLLUP_DTYPE.itemsize:
        raise ValueError(
            f"rollup payload length {len(raw)} not a multiple of "
            f"{ROLLUP_DTYPE.itemsize}")
    arr = np.frombuffer(raw, ROLLUP_DTYPE)
    return {int(r["block_start"]): (int(r["digest"]), int(r["n_series"]))
            for r in arr}


def local_rollup_digests(db, namespace: str, shard_id: int
                         ) -> dict[int, tuple[int, int]]:
    """{block_start: (rollup digest, n_series)} over this node's flushed
    volumes for one shard. O(1) per block after the first computation —
    digests cache on the immutable FilesetReader, so a repair cycle over
    an in-sync shard costs a dict walk, not a data pass."""
    ns = db.namespaces.get(namespace)
    if ns is None or shard_id not in ns.shards:
        return {}
    out: dict[int, tuple[int, int]] = {}
    for bs, reader in list(ns.shards[shard_id]._filesets.items()):
        try:
            out[bs] = (reader.rollup_digest(), reader.n_series)
        except ValueError:
            # captured reader closed by a concurrent flush swap + retire
            # drain: skip; the next cycle sees the new volume
            continue
    return out


class InProcessPeer:
    """Peer backed by a Database in the same process (integration/test)."""

    def __init__(self, db):
        self.db = db

    def _reader(self, namespace: str, shard: int, block_start: int):
        ns = self.db.namespaces.get(namespace)
        if ns is None or shard not in ns.shards:
            return None
        return ns.shards[shard]._filesets.get(block_start)

    def block_starts(self, namespace: str, shard: int) -> list[int]:
        ns = self.db.namespaces.get(namespace)
        if ns is None or shard not in ns.shards:
            return []
        return ns.shards[shard].flushed_block_starts

    def block_metadata(self, namespace, shard, block_start):
        reader = self._reader(namespace, shard, block_start)
        out = {}
        if reader is None:
            return out
        for i in range(reader.n_series):
            sid, _tags, stream = reader.read_at(i)
            out[sid] = {"checksum": zlib.adler32(stream), "size": len(stream)}
        return out

    def stream_block(self, namespace, shard, block_start, series_id):
        reader = self._reader(namespace, shard, block_start)
        if reader is None:
            return b"", b""
        return reader.read(series_id) or b"", reader.tags_of(series_id) or b""

    def rollup_digests(self, namespace, shard):
        return local_rollup_digests(self.db, namespace, shard)

    def flush_shard(self, shard):
        return self.db.flush_shard(shard)


class PeerClientError(Exception):
    """A peer answered with a deterministic 4xx (e.g. a namespace it
    doesn't have): the REQUEST is wrong, the host is healthy. Never
    retried and never counted against the host's circuit — one bad probe
    must not open a shared breaker and stall bootstrap of everything else
    that peer serves."""


# per-host breaker+retry policies shared by every HTTPPeer talking to the
# same base URL: bootstrap and repair often build several peer objects per
# replica, and they must share one circuit so a dead peer is shed
# process-wide instead of serializing a fresh timeout per object
PEER_POLICY_CONFIG = BreakerConfig(
    failure_threshold=3,
    open_timeout_s=2.0,
    retry_attempts=3,
    retry_backoff_s=0.05,
    retry_jitter_frac=0.25,  # de-synchronize replicas re-probing a peer
)
_host_policies: dict[str, HostPolicy] = {}
_host_policies_lock = threading.Lock()


def peer_policy(base_url: str, config: BreakerConfig | None = None) -> HostPolicy:
    with _host_policies_lock:
        pol = _host_policies.get(base_url)
        if pol is None:
            pol = HostPolicy(base_url, config or PEER_POLICY_CONFIG,
                             no_count=(PeerClientError,))
            _host_policies[base_url] = pol
        return pol


def reset_peer_policies() -> None:
    """Drop all shared peer breaker state (tests)."""
    with _host_policies_lock:
        _host_policies.clear()


class HTTPPeer:
    """Peer over the dbnode NodeAPI (services/dbnode.py).

    Every request runs through the host's shared CircuitBreaker + bounded
    jittered retry (client/breaker.py): transient errors get a couple of
    backed-off retries, and a dead peer opens the circuit so
    bootstrap/repair shed it locally (BreakerOpen, caught by the callers'
    per-peer error handling) instead of serializing 10s urlopen timeouts
    per block."""

    # process-wide default request timeout; dbnode config / the
    # m3_tpu.repair KV key override it per-peer (repair.peer_timeout_s) so
    # one slow replica cannot pin a 10s stall into every probe
    DEFAULT_TIMEOUT_S = 10.0

    def __init__(self, base_url: str, timeout_s: float | None = None,
                 policy: HostPolicy | None = None):
        self.base = base_url.rstrip("/")
        self.timeout = (timeout_s if timeout_s is not None
                        else self.DEFAULT_TIMEOUT_S)
        self.policy = policy if policy is not None else peer_policy(self.base)

    def _get(self, path: str):
        return self.policy.call(self._fetch, path)

    def _get_raw(self, path: str, accept: str):
        """GET returning (content_type, raw_payload) — the binary-frame
        negotiation seam (utils/wire.py): Accept advertises the frame
        codec; the caller dispatches on the Content-Type that came back."""
        return self.policy.call(self._fetch, path, None, accept)

    def _post(self, path: str, doc: dict):
        return self.policy.call(self._fetch, path, json.dumps(doc).encode())

    def _fetch(self, path: str, body: bytes | None = None,
               accept: str | None = None):
        import urllib.error

        from m3_tpu.utils import trace
        from m3_tpu.utils.instrument import default_registry

        with trace.span(trace.PEER_HTTP, peer=self.base), \
                default_registry().root_scope("peer").histogram(
                    "http_seconds"):
            faults.check("peer.http", url=self.base + path)
            headers = trace.inject_headers()
            if accept is not None:
                headers["Accept"] = accept
            req = urllib.request.Request(self.base + path, data=body,
                                         headers=headers)
            try:
                with urllib.request.urlopen(req, timeout=self.timeout) as r:
                    if accept is not None:
                        return (r.getheader("Content-Type") or
                                "application/json"), r.read()
                    return json.loads(r.read())
            except urllib.error.HTTPError as e:
                if e.code == 429:
                    # admission-control shed: backpressure (honored
                    # Retry-After + jittered retry in HostPolicy), NOT a
                    # client error and NOT a breaker failure
                    from m3_tpu.client.breaker import Backpressure
                    from m3_tpu.client.http_conn import _retry_after_s

                    raise Backpressure(
                        f"429 from {self.base}{path}",
                        retry_after_s=_retry_after_s(
                            e.headers.get("Retry-After")),
                    ) from e
                if 400 <= e.code < 500:
                    raise PeerClientError(
                        f"{e.code} from {self.base}{path}") from e
                raise

    def block_starts(self, namespace, shard):
        from urllib.parse import quote

        return [int(b) for b in self._get(
            f"/blocks/starts?namespace={quote(namespace, safe='')}"
            f"&shard={shard}"
        )]

    def block_metadata(self, namespace, shard, block_start):
        from urllib.parse import quote

        doc = self._get(
            f"/blocks/metadata?namespace={quote(namespace, safe='')}"
            f"&shard={shard}&block_start={block_start}"
        )
        return {
            base64.b64decode(k): v for k, v in doc.items()
        }

    def stream_block(self, namespace, shard, block_start, series_id):
        from urllib.parse import quote

        from m3_tpu.utils import wire

        # URL-encode the base64: '+' would decode as a space in query strings
        sid = quote(base64.b64encode(series_id).decode(), safe="")
        path = (f"/blocks/stream?namespace={quote(namespace, safe='')}"
                f"&shard={shard}&block_start={block_start}&series_id={sid}")
        if wire.packed_enabled():
            ctype, payload = self._get_raw(path, wire.CONTENT_TYPE)
            wire.account("stream_block", recv=len(payload))
            if wire.is_packed(ctype):
                stream, tags = wire.unpack_blobs(payload, wire.KIND_BLOCK)
                return stream, tags
            # mixed-version fleet: older peer answered JSON — parse it,
            # never fail the repair/bootstrap pull
            wire.count_fallback("server_json")
            doc = json.loads(payload)
        else:
            doc = self._get(path)
        return (base64.b64decode(doc["stream"]), base64.b64decode(doc["tags"]))

    def rollup_digests(self, namespace, shard):
        from urllib.parse import quote

        from m3_tpu.utils import wire

        path = (f"/blocks/rollup?namespace={quote(namespace, safe='')}"
                f"&shard={shard}")
        if wire.packed_enabled():
            ctype, payload = self._get_raw(path, wire.CONTENT_TYPE)
            wire.account("rollup", recv=len(payload))
            if wire.is_packed(ctype):
                (packed,) = wire.unpack_blobs(payload, wire.KIND_ROLLUP)
                return unpack_rollup(packed)
            wire.count_fallback("server_json")
            doc = json.loads(payload)
        else:
            doc = self._get(path)
        return unpack_rollup(base64.b64decode(doc.get("rollup_b64", "")))

    def flush_shard(self, shard):
        """Donor buffer/WAL tail handoff (shard handoff cutover safety):
        make the peer flush every buffered window of this shard so its
        rollup digests cover acked-but-unflushed writes — without this,
        cutover would verify against stale filesets and the donor's
        mutable window would die with the LEAVING shard."""
        doc = self._post("/shards/flush", {"shard": int(shard)})
        return int(doc.get("flushed", 0))


def bootstrap_shard_from_peers(db, namespace: str, shard_id: int,
                               peers: list[PeerSource],
                               known_starts: set[int] | None = None,
                               pacer=None) -> int:
    """Stream every flushed block a replica set has for this shard into
    local fileset volumes (the new-node bootstrap path). Returns blocks
    written. Majority checksum wins when peers disagree. Callers that
    already probed the peers' block starts pass them via known_starts to
    avoid re-fetching.

    `pacer` (optional, `.acquire(n_bytes)`) is the repair plane's token
    bucket: every stream pulled off a peer pays into the shared budget so
    a mass reassignment cannot starve foreground reads (the same storm-
    safety discipline `repair_shard_block` applies)."""
    ns = db.namespaces[namespace]
    shard = ns.shards[shard_id]
    if known_starts is not None:
        all_starts = set(known_starts)
    else:
        all_starts = set()
        for p in peers:
            try:
                all_starts.update(p.block_starts(namespace, shard_id))
            except faults.SimulatedCrash:
                # a crash injected at the peer.http seam is THIS process
                # dying mid-probe, not the peer being down: it must never
                # degrade into "peer adds no blocks" (that would falsify
                # every chaos assertion downstream)
                faults.escalate()
                raise
            except Exception:  # noqa: BLE001 - unreachable peer adds none
                pass
    streamed: list[int] = []
    for bs in sorted(all_starts):
        if bs in shard._filesets:
            continue  # already have a volume
        merged = _merged_block_from_peers(namespace, shard_id, bs, peers,
                                          pacer=pacer)
        if not merged:
            continue
        writer = FilesetWriter(
            shard.fs_root, namespace, shard_id, bs,
            ns.opts.retention.block_size_ns, volume=0,
        )
        for sid, (tags, stream) in sorted(merged.items()):
            writer.write_series(sid, tags, stream)
        writer.close()
        from m3_tpu.storage.fileset import FilesetReader

        shard._filesets[bs] = FilesetReader(
            shard.fs_root, namespace, shard_id, bs, 0
        )
        # with the swap, whatever comes after it: a lost bump would leave
        # a hot-tier entry over this block stale for good
        shard.bump_data_version(bs)
        streamed.append(bs)
    # the reverse index learns the streamed series (spanning every index
    # block the data block overlaps, like fs bootstrap)
    if ns.index is not None:
        from m3_tpu.utils.ident import decode_tags

        try:
            for bs in sorted(all_starts):
                reader = shard._filesets.get(bs)
                if reader is None:
                    continue
                for i in range(reader.n_series):
                    sid, tags_blob = reader.entry_at(i)
                    if tags_blob:
                        ns.index_insert_spanning(
                            sid, decode_tags(tags_blob), bs)
        finally:
            # and again once a fetch finds the series as well as the
            # volume (or as far as the index got): one in between keyed a
            # match without them under the swap's version
            for bs in streamed:
                shard.bump_data_version(bs)
    return len(streamed)


def _merged_block_from_peers(namespace, shard_id, bs, peers, pacer=None):
    """(series -> (tags, stream)) agreed by majority checksum; divergent
    series fall back to the first non-empty stream."""
    metas = []
    for p in peers:
        try:
            metas.append(p.block_metadata(namespace, shard_id, bs))
        except faults.SimulatedCrash:
            faults.escalate()  # our own injected death, not a peer error
            raise
        except Exception:  # noqa: BLE001 - unreachable peer contributes none
            metas.append({})
    all_sids = set()
    for m in metas:
        all_sids.update(m)
    out = {}
    for sid in all_sids:
        checksums: dict[int, int] = {}
        for m in metas:
            if sid in m:
                c = m[sid]["checksum"]
                checksums[c] = checksums.get(c, 0) + 1
        best = max(checksums.items(), key=lambda kv: kv[1])[0] if checksums else None
        for p, m in zip(peers, metas):
            if sid in m and (best is None or m[sid]["checksum"] == best):
                try:
                    stream, tags = p.stream_block(namespace, shard_id, bs, sid)
                except faults.SimulatedCrash:
                    faults.escalate()
                    raise
                except Exception:  # noqa: BLE001 - try the next replica
                    continue
                if stream:
                    if pacer is not None:
                        pacer.acquire(len(stream))
                    out[sid] = (tags, stream)
                    break
    return out


@dataclass
class RepairResult:
    checked: int = 0
    diverged: int = 0
    repaired: int = 0


def repair_shard_block(db, namespace: str, shard_id: int, block_start: int,
                       peers: list[PeerSource],
                       pacer=None) -> RepairResult:
    """Compare this node's block against peers and merge differences.

    The reference compares sizes/checksums then streams + merges differing
    blocks; here divergent series are decoded from every replica, merged
    last-write-wins, re-encoded, and written as a higher volume.

    Convergence: replica streams for one series merge in a DETERMINISTIC
    order (sorted by stream checksum) so two replicas repairing against
    each other resolve a same-timestamp value conflict to the SAME winner
    — otherwise each side would adopt the other's value and oscillate
    forever, and the rig's digest-equality audit could never settle.

    `pacer` (optional, `.acquire(n_bytes)`) is the RepairDaemon's token
    bucket: every stream pulled off a peer pays into the repair budget so
    a post-outage repair storm cannot starve the serving path.

    Locking: the slow phase (peer RPCs, decode/merge/re-encode) runs
    OUTSIDE the shard maintenance lock so a repair over slow peers never
    stalls the tick's flush/expire. Only the volume write + swap takes the
    lock; if a flush swapped in a new volume meanwhile, the merge is stale
    and is abandoned for the next repair cycle to redo.
    """
    from m3_tpu.encoding.m3tsz import Encoder
    from m3_tpu.encoding.m3tsz import decode as scalar_decode

    ns = db.namespaces[namespace]
    shard = ns.shards[shard_id]
    with shard._maint_lock:
        reader = shard._filesets.get(block_start)
    local_meta = {}
    result = RepairResult()
    try:
        if reader is not None:
            for i in range(reader.n_series):
                sid, _tags, stream = reader.read_at(i)
                local_meta[sid] = zlib.adler32(stream)
    except ValueError:
        # captured reader closed by a concurrent flush + retire-grace
        # expiry; stale pass, redo next cycle
        return result
    peer_metas = []
    for p in peers:
        try:
            peer_metas.append(p.block_metadata(namespace, shard_id, block_start))
        except faults.SimulatedCrash:
            faults.escalate()  # our own injected death, not a peer error
            raise
        except Exception:  # noqa: BLE001 - unreachable peer contributes none
            peer_metas.append({})
    all_sids = set(local_meta)
    for m in peer_metas:
        all_sids.update(m)
    result.checked = len(all_sids)

    divergent: list[bytes] = []
    for sid in all_sids:
        local = local_meta.get(sid)
        for m in peer_metas:
            if sid in m and m[sid]["checksum"] != local:
                divergent.append(sid)
                break
    result.diverged = len(divergent)
    if not divergent:
        return result

    unit = ns.opts.write_time_unit
    merged: dict[bytes, tuple[bytes, bytes]] = {}
    for sid in divergent:
        parts_t, parts_v = [], []
        streams = []
        try:
            tags = reader.tags_of(sid) if reader else None
            own = reader.read(sid) if reader is not None else None
        except ValueError:
            # a merge slower than the retire grace can find the captured
            # reader closed after a concurrent flush; the merge is stale
            # either way (the swap check below would abandon it), so bail
            # now and let the next repair cycle re-compare
            result.repaired = 0
            return result
        have = set()
        if own:
            streams.append(own)
            have.add(local_meta[sid])
        for p, m in zip(peers, peer_metas):
            if m:
                pm = m.get(sid)
                if pm is None:
                    continue  # peer's own metadata says it lacks this series
                if pm["checksum"] in have:
                    # byte-identical to a stream already in hand (ours or a
                    # previously fetched peer's): re-pulling it buys the
                    # merge nothing and charges the repair rate budget —
                    # under RF=3 that's roughly half the storm's wire cost
                    continue
            try:
                stream, ptags = p.stream_block(namespace, shard_id, block_start, sid)
            except faults.SimulatedCrash:
                faults.escalate()
                raise
            except Exception:  # noqa: BLE001 - peer unreachable mid-stream
                continue
            if stream:
                if pacer is not None:
                    pacer.acquire(len(stream))
                streams.append(stream)
                tags = tags or ptags
                have.add(zlib.adler32(stream))
        # deterministic merge order: both sides of a replica pair must
        # concatenate the same streams in the same order so last-write-wins
        # picks the same value for a conflicting timestamp on both nodes
        streams.sort(key=lambda s: (zlib.adler32(s), s))
        for stream in streams:
            dps = scalar_decode(stream, int_optimized=ns.opts.int_optimized,
                                default_time_unit=unit)
            if dps:
                parts_t.append(np.array([d.timestamp_ns for d in dps], np.int64))
                parts_v.append(
                    np.array([d.value for d in dps], np.float64).view(np.uint64)
                )
        if not parts_t:
            continue
        times, vbits = merge_dedup(np.concatenate(parts_t), np.concatenate(parts_v))
        enc = Encoder(block_start, int_optimized=ns.opts.int_optimized,
                      default_time_unit=unit)
        for t, vb in zip(times, vbits):
            enc.encode(int(t), float(np.uint64(vb).view(np.float64)), unit)
        merged[sid] = (tags or b"", enc.stream())
        result.repaired += 1

    if not merged:
        # nothing could actually be streamed (e.g. peers unreachable):
        # writing an empty volume would mask the block forever
        result.repaired = 0
        return result

    with shard._maint_lock:
        if shard._filesets.get(block_start) is not reader:
            # a flush swapped in a new volume while we merged: our result
            # is stale; the next repair cycle re-compares against it
            result.repaired = 0
            return result
        # write a higher volume carrying merged + untouched series
        volume = (reader.volume + 1) if reader else 0
        writer = FilesetWriter(
            shard.fs_root, namespace, shard_id, block_start,
            ns.opts.retention.block_size_ns, volume,
        )
        seen = set()
        for sid, (tags, stream) in sorted(merged.items()):
            writer.write_series(sid, tags, stream)
            seen.add(sid)
        if reader is not None:
            for i in range(reader.n_series):
                sid, tags, stream = reader.read_at(i)
                if sid not in seen:
                    writer.write_series(sid, tags, stream)
        writer.close()
        from m3_tpu.storage.fileset import FilesetReader

        if reader is not None:
            # retire, don't close: a concurrent Shard.read may still hold
            # this reader from its snapshot (see Shard._retire)
            shard._retire(reader)
        shard._filesets[block_start] = FilesetReader(
            shard.fs_root, namespace, shard_id, block_start, volume
        )
        # with the swap, whatever comes after it: a lost bump would leave
        # a hot-tier entry over this block stale for good
        shard.bump_data_version(block_start)
        if shard.cache is not None:  # cached decodes predate the repair
            shard.cache.invalidate_block(namespace, shard_id, block_start)
    # peer-only series become queryable
    if ns.index is not None:
        from m3_tpu.utils.ident import decode_tags

        try:
            for sid, (tags, _stream) in merged.items():
                if tags:
                    ns.index_insert_spanning(sid, decode_tags(tags),
                                             block_start)
        finally:
            # and again once a fetch finds the peer-only series as well
            # as the volume (or as far as the index got): one in between
            # keyed a match without them under the swap's version
            shard.bump_data_version(block_start)
    return result
