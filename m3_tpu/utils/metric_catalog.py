"""The histogram/timer name catalog — the exposition contract.

Every literal name passed to ``Scope.observe`` / ``Scope.histogram`` /
``Scope.histogram_handle`` / ``Scope.timer`` in m3_tpu must be listed
here (m3lint rule ``inv-histogram-catalog``).  The catalog is what
dashboards, the self-scrape (`_m3_system`) queries, and the OpenMetrics
exemplar links are written against: a histogram that exists only at its
call site is a metric nobody can alert on, and a renamed one silently
breaks every recorded query.

Names are the LEAF names (the scope prefix supplies the subsystem, e.g.
``storage.db`` + ``write_batch_seconds``).  Keep the set literal — the
lint parses it with ``ast.literal_eval`` and never imports this module.
"""

from __future__ import annotations

HISTOGRAMS = {
    # storage / durability plane
    "write_seconds",            # storage.db per-point write
    "write_batch_seconds",      # storage.db fused batch write
    "write_batch_size",         # storage.db entries per batch
    "read_many_seconds",        # storage.ns fused batch read
    "shard_flush_seconds",      # shard warm flush
    "commitlog_fsync_seconds",  # WAL fsync wall time
    "persist_seconds",          # fileset/index/kv persist (per-scope)
    # compute plane
    "seconds",                  # decode/encode + rpc legs (per-scope);
    #                             also compute.execute{op,sig} — the
    #                             compute_execute_seconds exposition
    #                             family: wall time of one tracked
    #                             cache-HIT program call (dispatch,
    #                             device execution and the wait for
    #                             the result; sig is the shape-bucket
    #                             signature, <=64 distinct labels then
    #                             "other"); and query.stage{route,
    #                             stage}, the stage clock's self-time
    "batch_size",               # decode.batch per-rung batch size
    "compile_seconds",          # compute.jit trace+compile on cache miss
    "plan_compile_seconds",     # compute.query_plan whole-plan compile
    #                             on a plan-shape cache miss (ROADMAP #2)
    # cluster / messaging plane
    "append_seconds",           # consensus append-entries
    "commit_seconds",           # consensus majority commit
    "send_seconds",             # msg producer
    "recv_seconds",             # msg consumer
    "http_seconds",             # storage peers HTTP
    "cycle_seconds",            # repair daemon anti-entropy cycle
    # client / query plane
    "fetch_many_seconds",       # session batched fetch
    "request_seconds",          # coordinator request + per-tenant SLO
    "flush_seconds",            # aggregator flush
    # pipelined dataflow (storage/pipeline)
    "stage_seconds",            # pipeline.stage{stage=gather|decode}:
    #                             per-run stage-time sums; compared with
    #                             the run's wall time they expose overlap
    # profiling & saturation plane (utils/profiler)
    "sample_seconds",           # profiler per-pass sampling wall time
    "wait_seconds",             # lock.wait_seconds{cls=site}: per-class
    #                             acquire-wait (published via
    #                             merge_histogram at snapshot time)
    # paged columnar memory & device-resident hot tier (ROADMAP #3)
    "page_fill",                # storage.page_pool: fraction of a sealed
    #                             window's page allocation holding real
    #                             rows (padding-waste measure, observed
    #                             at every ragged seal)
    "hot_tier_entry_bytes",     # storage.hot_tier: resident bytes of one
    #                             prepared-slab entry at admission
    # device-compiled inverted index (ROADMAP #4)
    "postings_seconds",         # compute.index: wall time of one fused
    #                             postings-program call (index/device.py;
    #                             a shape-cache miss includes compile —
    #                             compute.jit{op=postings_program} splits
    #                             hit/miss and compile time out)
    # standing-query plane (ROADMAP #2, query/standing.py)
    "rule_eval_lag_seconds",    # aggregator.standing: how far behind
    #                             real time a rule's last evaluated grid
    #                             point was when its re-evaluation
    #                             started (bounded-lag contract the
    #                             standing_rules rig episode audits)
}

TIMERS = {
    "tick",                     # coordinator/dbnode tick loops
}

# Non-histogram families the profiling & saturation plane exports —
# documented here so dashboards have one contract file to read (the
# lint only enforces the histogram/timer sets above):
#   queue_depth / queue_capacity / queue_dropped {queue=...}  gauges
#       refreshed at every registry snapshot (instrument.monitor_queue)
#   lock_acquisitions / lock_contended {cls=...}              counters
#   watchdog_loop_stalls {loop=...}                           counter
#   profiler_samples / profiler_evicted_samples               (status
#       JSON on /debug/profile; not registry families)
#
# Sharded compute plane (PR 12) mesh-dispatch counter families, under
# the compute.mesh scope with a {devices=N} label:
#   compute_mesh_dispatch {devices=...}        fused queries served on
#       the series-sharded device mesh (query/compiler._execute)
#   compute_mesh_skew_fallback {devices=...}   sharded dispatch declined
#       because the series->sample distribution was too skewed for
#       balanced slabs (ran the single-device program instead)
# plus the dispatch-layer tallies query.compile[sharded] and
# windowed_agg.aggregate_groups[mesh] on /debug counters.
#
# Paged columnar memory & device-resident hot tier (ROADMAP #3):
#   queue_depth/capacity/dropped {queue=page_pool}   pages in use /
#       pages resident / pages evicted back to the OS, aggregated over
#       every shard's pool (storage/pagepool.monitor_pool)
#   storage_page_pool_resident_bytes                 gauge refreshed by
#       the pagepool snapshot hook
#   queue_depth/capacity/dropped {queue=hot_tier}    prepared-slab bytes
#       used / byte cap / LRU evictions (storage/hottier)
#   storage_hot_tier_hit / storage_hot_tier_miss     per-plan counters
#       (compiled path; the same outcome rides the ?explain=analyze
#       hot_tier block); an entry misses once the version of a block
#       its fetch's range touches has moved (Namespace.data_version_in),
#       not on a write to any other block
#   storage_hot_tier_fetch_skipped                   plans served from a
#       warm entry with no index match and no read
#
# Series -> shard routing (storage/sharding.py ShardRoutes):
#   storage_shard_route_hit / storage_shard_route_miss   series ids
#       routed from a namespace's remembered routes / hashed with
#       murmur3 (first sight, or not routed for two block periods);
#       added to once per batched read, write or standing-rule probe
#
# Device-compiled inverted index (ROADMAP #4), compute.index scope:
#   compute_index_device                       segments whose boolean
#       postings algebra ran as ONE fused ragged program
#       (index/device.py match)
#   compute_index_fallback {reason=...}        segments that took the
#       counted scalar walk instead — reason is one of
#       unpacked_segment / nested_boolean / trivial_query /
#       small_work; the same split rides the
#       ?explain=analyze `index` block per query
# plus the dispatch-layer tallies index.postings[device|host] and
# jit_postings_program[hit|miss] on /debug counters.
#
# Topology elasticity (PR 17), placement scope — the off-tick handoff
# controller (services/handoff.py) and the client-plane placement
# watcher (client/topology_watch.py):
#   placement_sync_deferred {reason=...}       handoffs that could NOT
#       safely cut over this pass — reason is one of unreachable /
#       tail_flush_failed / digests_diverged / no_placement; each defer
#       also emits the placement.sync.defer tracepoint with the shard id
#   placement_cutover_failures                 mark_available CAS lost
#       (KV contention/outage); the shard re-enters the handoff lane on
#       the next placement sync
#   placement_handoff_errors                   a shard handoff aborted on
#       an unexpected error (retried next sync)
#   session_topology_version                   gauge: the placement KV
#       version the client session's TopologyMap was last hot-swapped
#       to; lag against the KV's own version is swap latency
#
# Standing-query plane (ROADMAP #2), aggregator.standing scope — one
# counter bump per rule per flush pass (query/standing.py evaluate):
#   aggregator_standing_rules_evaluated        rules whose invalidated
#       grid actually re-evaluated (compiled plan ran, outputs written)
#   aggregator_standing_rules_invalidated      rules whose input shards'
#       data_version bumps (or bootstrap/placement change) invalidated
#       their last evaluation key
#   aggregator_standing_rules_skipped          rules whose (data_version,
#       selector, grid) identity was unchanged — no sample reads, no
#       evaluation (the steady-state incremental win)
#   aggregator_standing_rules_errors           rule evaluations aborted
#       on an error (bad out-of-band expr, storage failure); the rule
#       retries next flush
#
# Device-compute observability plane (utils/compute_stats +
# dispatch.jit_tracker; the /debug/compute payload renders the same
# ledger as JSON on all four services):
#   compute_execute_seconds {op,sig}           histogram (the cataloged
#       "seconds" leaf under compute.execute) — the per-program
#       device-time attribution
#   compute_jit_cache_evictions {op}           counter: executable-cache
#       entries that vanished between tracked calls (clear_caches,
#       donated/evicted executables) — the miss-accounting ground truth
#   compute_waste_logical_elements /
#   compute_waste_padded_elements /
#   compute_waste_waste_ratio {site,axis}      gauges refreshed by the
#       compute_stats snapshot hook: real vs half-octave/slab-padded
#       elements at every padding seam (site in query_slabs / postings /
#       encode_ragged / decode_batch / windowed_agg)
#   compute_device_cache_* {cache=...}         gauges (entries, bytes,
#       bf16_bytes, ...) from registered device-resident cache
#       providers: the hot tier (storage/hottier) and the per-segment
#       postings columns (index/packed)
#
# The stage clock of the served query path (utils/trace.py stage()),
# query.stage scope, fed for every request whatever the trace sampling
# (a thread's stages are published together, in one acquisition of the
# registry's lock, when its outermost span closes):
#   query_stage_seconds {route,stage}          histogram: a stage's wall
#       SELF-time (own minus what the stages beneath it covered on its
#       thread), one observation per span; route is query_range / query /
#       remote_write / remote_read / other (query/api.py _ROUTES), stage
#       a trace.STAGE_* name. Per route the request thread's stages sum
#       to the root stage `request`'s whole duration
#   query_stage_cpu_seconds {route,stage}      counter: the same spans'
#       thread CPU self-time (time.thread_time_ns); wall minus CPU is
#       time the thread waited (GIL, locks, the device)
# The write route's stages under `request`: write.decode, write.batch,
# write.commitlog, write.buffer. The mediator's cycle is a route of its
# own, `tick` (storage/database.py tick, outside any request): root
# `tick`, tick.snapshot.host, tick.flush, tick.rotate, and beneath the
# snapshot (or a flush) encode.device_wait, the device encoder's call
# (encoding/m3tsz/hostpath.py encode_blocks, on any route); the root's
# _count is the cycles. Beside them, storage scope:
#   storage_snapshot_samples / storage_snapshot_bytes   samples the
#       tick's snapshots sealed and the stream bytes their encoder
#       wrote (storage/shard.py snapshot)
#   storage_commitlog_rotations                commitlog files retired
#       (storage/database.py _rotate_commitlog)
#
# Tier-resolution read routing (query/resolver.resolve_read), query.tier
# scope with a {tier=...} label (raw / stitched / pinned_raw /
# aggregated_<res>s — bounded by distinct tier resolutions):
#   query_tier_reads {tier=...}                selector fetches served
#       by each tier choice; the same decision rides ?explain=analyze
#       as the per-fetch `tiers` block
#
# Binary wire plane (utils/wire, ROADMAP #1) — the bytes-on-wire ledger
# for the fat inter-node flows, counted by the CLIENT side of each flow
# (one unambiguous owner per counter: the coordinator accounts
# read_batch + response, a repairing/bootstrapping dbnode accounts
# stream_block + rollup); the rig surfaces the sums as the
# net_bytes_total trajectory column:
#   net_bytes_sent {flow=read_batch|stream_block|rollup|response}
#       request/response bytes written to the wire for that flow
#   net_bytes_recv {flow=...}                  bytes read off the wire
#   net_wire_fallback {reason=server_json|client_json}
#       a packed-capable side served/parsed legacy JSON instead
#       (mixed-version fleet); every bump also emits the wire.fallback
#       tracepoint — counted, never an error
