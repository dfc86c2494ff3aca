"""Distributed query/aggregation kernels over the (shard x replica) mesh.

These are the XLA-collective replacements for the reference's network
fan-outs (SURVEY.md §2.11):

- cross-shard rollup: coordinator scatter/gather + aggregator forwarding
  (query/storage/m3/storage.go:286-496, aggregator forwarded_writer.go)
  becomes a local segment reduction + psum over the 'shard' ICI axis;
- replica divergence detection: the background repair's metadata checksum
  comparison (storage/repair.go:839) becomes an all_gather over 'replica'
  + elementwise compare, entirely device-resident;
- time-sharded windowed sums: long-range queries shard the time axis and
  exchange window-boundary partials with ppermute — the ring pattern
  (SURVEY.md §5 long-context analog) instead of materializing the range on
  one host.

All kernels are shard_map'd SPMD programs: jit once, run on every device.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

import m3_tpu.ops  # noqa: F401  (x64)


def sharded_group_sum(values, group_ids, n_groups: int, mesh):
    """Global per-group (sum, count) of series sharded over 'shard'.

    values: [S, T] f64 sharded on S; group_ids: [S] int32 (global group
    space). Returns replicated [G, T] sums and [G] counts.
    """

    def local(values, group_ids):
        seg = jax.ops.segment_sum(values, group_ids, num_segments=n_groups)
        cnt = jax.ops.segment_sum(
            jnp.ones(values.shape[0], jnp.int32), group_ids, num_segments=n_groups
        )
        total = lax.psum(seg, "shard")
        count = lax.psum(cnt, "shard")
        if mesh.shape.get("replica", 1) > 1:
            # each replica already computed the exact global total (the
            # psum runs over 'shard' only); the pmean of identical values
            # just marks the result replicated over 'replica' for out_specs
            total = lax.pmean(total, "replica")
            count = lax.pmean(count, "replica")
        return total, count

    f = shard_map(
        local, mesh=mesh,
        in_specs=(P("shard", None), P("shard")),
        out_specs=(P(None, None), P(None)),
    )
    return f(values, group_ids)


def replica_divergence(series_checksums, mesh):
    """Detect replica divergence: [S] uint64 per-series block checksums,
    sharded on 'shard', replicated on 'replica'. Returns [S] bool sharded
    like the input: True where any replica disagrees (repair candidates)."""

    def local(cs):
        everyone = lax.all_gather(cs, "replica")  # [R, S_local]
        diverged = (everyone != everyone[0:1]).any(axis=0)
        # pmax makes the (already identical) result explicitly replicated
        # across 'replica' so the out_spec's replication is inferable
        return lax.pmax(diverged.astype(jnp.int32), "replica").astype(bool)

    f = shard_map(
        local, mesh=mesh, in_specs=(P("shard"),), out_specs=P("shard")
    )
    return f(series_checksums)


def time_sharded_window_sums(values, mesh, points_per_window: int):
    """Windowed sums over a time axis sharded across 'shard'.

    values: [S, T] with T sharded. Windows of `points_per_window` columns
    may straddle device boundaries; each device computes its local partial
    windows and the straddling head/tail partials ride a ppermute ring to
    the neighbor that owns the window start — the blockwise/ring pattern.
    Requires T % shard == 0. Returns [S, T // points_per_window] sums
    replicated across the mesh.
    """
    n_dev = mesh.shape["shard"]
    if values.shape[1] % points_per_window != 0:
        raise ValueError(
            f"time axis {values.shape[1]} not a multiple of window "
            f"{points_per_window} (trailing columns would be dropped)"
        )

    def local(vals):
        S, t_local = vals.shape
        idx = lax.axis_index("shard")
        t0 = idx * t_local  # global column offset of this device's slab
        w = points_per_window
        col = t0 + jnp.arange(t_local)
        wid = col // w  # global window id per local column
        n_windows_total = (t_local * n_dev) // w
        partial = jax.ops.segment_sum(
            vals.T, wid, num_segments=n_windows_total, indices_are_sorted=True
        ).T  # [S, W_total] local partials
        # windows are disjoint per column, so a psum combines straddling
        # partials exactly (each device contributed its own columns)
        return lax.psum(partial, "shard")

    f = shard_map(local, mesh=mesh, in_specs=(P(None, "shard"),),
                  out_specs=P(None, None))
    return f(values)


def ring_shift_boundary(values, mesh):
    """One ppermute ring step over 'shard': each device receives its left
    neighbor's last column (the boundary-exchange primitive used when a
    computation needs its predecessor's tail, e.g. delta-of-delta across a
    time-shard split)."""

    def local(vals):
        last_col = vals[:, -1:]
        n = mesh.shape["shard"]
        recv = lax.ppermute(
            last_col, "shard", [(i, (i + 1) % n) for i in range(n)]
        )
        return recv

    f = shard_map(local, mesh=mesh, in_specs=(P(None, "shard"),),
                  out_specs=P(None, "shard"))
    return f(values)


def time_sharded_reset_adjust(values, mesh):
    """Sequence-parallel counter monotonization: reset-adjust [S, T]
    counter samples whose TIME axis is sharded across 'shard'.

    The single-host form (query/windows._reset_adjusted, upstream
    Prometheus counter semantics) is a prefix computation over time —
    exactly the dependency ring/blockwise attention breaks for long
    sequences. Device-local work is one pass; the cross-device carry needs
    two tiny collectives (SURVEY.md §5 long-context analog):

      1. each device receives its LEFT neighbor's last column (ppermute
         ring) so a reset straddling the shard boundary is detected;
      2. per-device total drops all_gather into an EXCLUSIVE prefix over
         the mesh axis — the carry every device adds to its local
         cumulative drops.

    Returns the globally monotonized [S, T] matrix, sharded like the
    input. rate()/increase() over any window then reduces to
    last-minus-first regardless of which devices hold the window.
    """
    n = mesh.shape["shard"]

    def local(vals):
        # 1) boundary exchange: left neighbor's last column
        prev_col = lax.ppermute(
            vals[:, -1:], "shard", [(i, (i + 1) % n) for i in range(n)]
        )
        idx = lax.axis_index("shard")
        # device 0 has no predecessor: its first column can't be a reset
        prev = jnp.where(idx == 0, vals[:, :1], prev_col)
        shifted = jnp.concatenate([prev, vals[:, :-1]], axis=1)
        drop = jnp.where(vals < shifted, shifted, 0.0)
        local_cum = jnp.cumsum(drop, axis=1)
        # 2) exclusive prefix of per-device drop totals over the mesh axis
        totals = lax.all_gather(local_cum[:, -1], "shard")  # [n, S]
        mask = (jnp.arange(n) < idx)[:, None]
        carry = jnp.sum(totals * mask, axis=0)  # [S]
        return vals + local_cum + carry[:, None]

    f = shard_map(local, mesh=mesh, in_specs=(P(None, "shard"),),
                  out_specs=P(None, "shard"))
    return f(values)
