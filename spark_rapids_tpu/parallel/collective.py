"""Device-resident collective shuffle: hash partition + all_to_all, fused.

Reference: the UCX peer-to-peer shuffle (SURVEY.md §2.8 mode 3) keeps map
output ON DEVICE (ShuffleBufferCatalog) and moves blocks over RDMA with
bounce buffers and a flatbuffers control plane.  The TPU-native redesign
collapses all of that into one SPMD program per signature:

    per device (shard_map over the 1-D ``data`` mesh axis):
      1. stable-sort local rows by destination partition id
      2. pack rows into a [n_dev, B] send buffer (destination-major;
         quota = the full local bucket B, so no overflow is possible —
         ICI collectives need static shapes, SURVEY.md §7 hard part 3)
      3. ``lax.all_to_all`` the send buffer + per-destination counts
      4. compact received blocks to the front; the only host syncs are the
         per-device received totals

No serialization, no host copies, no heartbeat protocol: the collective IS
the transport, and partial-failure handling rides the runtime (a lost chip
fails the whole step — Spark-style stage retry re-runs it; the reference
reaches the same end state via fetch-failure => stage retry).

Data layout: "sharded batches" are global jax arrays of shape
[n_dev * B, ...] with axis 0 sharded over the mesh; each device owns a
padded local bucket B with its own logical row count (``counts`` vector,
one entry per device).
"""

from __future__ import annotations

from typing import List, Sequence

import numpy as np

from spark_rapids_tpu.columnar.batch import HostColumnarBatch
from spark_rapids_tpu.columnar.column import DeviceColumn, bucket_rows
from spark_rapids_tpu.parallel.mesh import MeshContext


def _jx():
    from spark_rapids_tpu.columnar.column import _jnp
    return _jnp()


def shard_batch(ctx: MeshContext, host_batches: Sequence[HostColumnarBatch]):
    """Distributes host batches round-robin to mesh devices: returns
    (cols, counts) in the sharded-batch layout above.  ``cols`` is a list
    of (data, validity, lengths) global arrays."""
    import jax
    jnp = _jx()
    n = ctx.num_devices
    per_dev: List[List[HostColumnarBatch]] = [[] for _ in range(n)]
    for i, hb in enumerate(host_batches):
        per_dev[i % n].append(hb)
    from spark_rapids_tpu.columnar.batch import concat_host_batches
    merged = [concat_host_batches(bs) if bs else host_batches[0].slice(0, 0)
              for bs in per_dev]
    B = bucket_rows(max(1, max(hb.row_count for hb in merged)))
    locals_ = [hb.to_device(B) for hb in merged]
    sharding = ctx.data_sharding()
    cols = []
    for ci in range(locals_[0].num_columns):
        parts_d = [lb.columns[ci].data for lb in locals_]
        parts_v = [lb.columns[ci].validity for lb in locals_]
        # string columns: align widths before stacking
        if locals_[0].columns[ci].lengths is not None:
            w = max(int(p.shape[1]) for p in parts_d)
            parts_d = [jnp.pad(p, ((0, 0), (0, w - p.shape[1])))
                       for p in parts_d]
            parts_l = [lb.columns[ci].lengths for lb in locals_]
            ln = jax.device_put(jnp.concatenate(parts_l), sharding)
        else:
            ln = None
        d = jax.device_put(jnp.concatenate(parts_d), sharding)
        v = jax.device_put(jnp.concatenate(parts_v), sharding)
        cols.append((d, v, ln))
    counts = jax.device_put(
        jnp.asarray([lb.row_count for lb in locals_], dtype=np.int64),
        ctx.data_sharding())
    return cols, counts


def unshard_batch(ctx: MeshContext, cols, counts,
                  dtypes, names=None) -> HostColumnarBatch:
    """Gathers a sharded batch back to one host batch (driver collect)."""
    import pyarrow as pa
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.batch import concat_host_batches
    from spark_rapids_tpu.columnar.column import HostColumn
    n = ctx.num_devices
    counts_h = np.asarray(counts)
    total_bucket = int(cols[0][0].shape[0])
    B = total_bucket // n
    # one device->host transfer per column; slices assembled host-side
    host = [(np.asarray(d), np.asarray(v),
             None if ln is None else np.asarray(ln)) for d, v, ln in cols]
    batches = []
    for dev in range(n):
        cnt = int(counts_h[dev])
        lo = dev * B
        dev_cols = []
        for (d, v, ln), dt in zip(host, dtypes):
            vv = v[lo:lo + cnt]
            if isinstance(dt, (T.StringType, T.BinaryType)):
                # packed-bytes repr: reuse the device column decoder
                dc = DeviceColumn(_jx().asarray(d[lo:lo + B]),
                                  _jx().asarray(v[lo:lo + B]), cnt, dt,
                                  _jx().asarray(ln[lo:lo + B]))
                dev_cols.append(dc.to_host())
            elif isinstance(dt, T.DecimalType) and dt.is_decimal128:
                # two-limb physical repr: reuse the device column decoder
                dc = DeviceColumn(_jx().asarray(d[lo:lo + B]),
                                  _jx().asarray(v[lo:lo + B]), cnt, dt)
                dev_cols.append(dc.to_host())
            else:
                dev_cols.append(HostColumn.from_numpy(d[lo:lo + cnt], vv,
                                                      dt))
        batches.append(HostColumnarBatch(dev_cols, cnt, names))
    return concat_host_batches(batches)


def shard_engine_batches(ctx: MeshContext, batches, schema):
    """Places engine batches (host or device ColumnarBatch) into the
    sharded-batch layout: the single-controller input-pipeline step of the
    SPMD model (scan output -> device_put with a NamedSharding); all
    subsequent shuffle/compute rides the mesh."""
    from spark_rapids_tpu.columnar.batch import (ColumnarBatch,
                                                 HostColumnarBatch)
    host = []
    for b in batches:
        if isinstance(b, ColumnarBatch):
            b = b.to_host()
        host.append(b)
    if not host:
        import pyarrow as pa
        from spark_rapids_tpu import types as T
        from spark_rapids_tpu.columnar.batch import batch_from_arrow
        empty = pa.table({f.name: pa.array([], type=T.to_arrow(f.data_type))
                          for f in schema.fields})
        host = [batch_from_arrow(empty)]
    return shard_batch(ctx, host)


def shard_to_batch(ctx: MeshContext, cols, counts, schema, shard: int):
    """Reduce-side read: materializes mesh shard ``shard`` as a regular
    engine ColumnarBatch (the reduce task's fetch; all data already sits on
    that device).

    The shard planes are COPIED (a device-local copy, no transfer):
    ``addressable_shards[i].data`` shares buffers with the exchange's
    global arrays, and downstream consumers legitimately register their
    input batches spillable and ``.delete()`` them (the out-of-core agg
    merge does) — deleting a shared buffer would poison the exchange
    store for every re-read of the same shard (task retry, plan
    reuse)."""
    jnp = _jx()
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    n = ctx.num_devices
    cnt = int(np.asarray(counts)[shard])
    out_cols = []
    for (d, v, ln), f in zip(cols, schema.fields):
        ds = jnp.copy(d.addressable_shards[shard].data)
        vs = jnp.copy(v.addressable_shards[shard].data)
        ls = None if ln is None else \
            jnp.copy(ln.addressable_shards[shard].data)
        out_cols.append(DeviceColumn(ds, vs, cnt, f.data_type, ls))
    return ColumnarBatch(out_cols, cnt,
                         [f.name for f in schema.fields])


def collective_hash_shuffle(ctx: MeshContext, cols, counts, pids):
    """The fused distributed shuffle.

    cols: [(data [n*B, ...], validity [n*B], lengths [n*B] | None)]
    counts: [n] per-device logical row counts
    pids: [n*B] destination device per row (int32, any value for padding)

    Returns (cols', counts') in the same layout: device d ends up with
    every row whose pid == d, bucket n*B per device.

    Chaos point ``parallel.collective`` fires here (a lost chip fails the
    whole SPMD step); the exchange catches the retryable failure and
    degrades to the host-staged per-partition path instead of failing
    the query.
    """
    from spark_rapids_tpu.aux.faults import maybe_fire
    maybe_fire("parallel.collective")
    import jax
    from jax.sharding import PartitionSpec as P
    from spark_rapids_tpu.ops.batch_ops import compaction_perm
    from spark_rapids_tpu.ops.sort_ops import lex_sort_perm
    jnp = _jx()
    n = ctx.num_devices
    total = int(cols[0][0].shape[0])
    B = total // n
    sig = tuple((str(d.dtype), tuple(d.shape), ln is not None)
                for d, v, ln in cols)
    mesh_key = tuple(d.id for d in ctx.mesh.devices.flat)
    key = (mesh_key, n, B, sig)

    def build():
        axis = ctx.data_axis

        def per_device(arrs, count, pids):
            # local shapes: arrs [B, ...], count [1], pids [B]
            count = count[0]
            rowpos = jnp.arange(B, dtype=np.int32)
            inrow = rowpos < count
            dest = jnp.where(inrow, jnp.clip(pids, 0, n - 1), n)
            # 1. destination-major stable order
            order = lex_sort_perm(
                [(dest.astype(np.uint32), n.bit_length())], B, jnp)
            sdest = jnp.take(dest, order)
            dcount = jnp.bincount(sdest, length=n + 1)[:n]
            doff = jnp.cumsum(dcount) - dcount
            # 2. pack [n, B] send buffers (slot = rank within destination)
            slot = rowpos - jnp.take(doff, jnp.clip(sdest, 0, n - 1))
            flat = jnp.where(sdest < n,
                             jnp.clip(sdest, 0, n - 1) * B + slot, n * B)
            send_counts = dcount.astype(np.int64)

            def pack(x):
                shape = (n * B,) + x.shape[1:]
                buf = jnp.zeros(shape, dtype=x.dtype)
                xs = jnp.take(x, order, axis=0)
                return buf.at[flat].set(xs, mode="drop") \
                    .reshape((n, B) + x.shape[1:])

            # 3. exchange: block d of my send buffer -> device d
            recv_counts = jax.lax.all_to_all(
                send_counts.reshape(n, 1), axis, 0, 0, tiled=False
            ).reshape(n)
            outs = []
            for (d, v, ln) in arrs:
                rd = jax.lax.all_to_all(pack(d), axis, 0, 0, tiled=False)
                rv = jax.lax.all_to_all(pack(v), axis, 0, 0, tiled=False)
                rl = None if ln is None else jax.lax.all_to_all(
                    pack(ln), axis, 0, 0, tiled=False)
                outs.append((rd, rv, rl))
            # 4. compact received blocks to the front
            blockpos = jnp.arange(B, dtype=np.int64)
            live = blockpos[None, :] < recv_counts[:, None]   # [n, B]
            live_flat = live.reshape(n * B)
            corder = compaction_perm(live_flat, jnp)
            new_count = jnp.sum(recv_counts)
            final = []
            for (rd, rv, rl) in outs:
                fd = jnp.take(rd.reshape((n * B,) + rd.shape[2:]), corder,
                              axis=0)
                fv = jnp.take(rv.reshape(n * B) & live_flat, corder, axis=0)
                fl = None if rl is None else jnp.take(rl.reshape(n * B),
                                                      corder, axis=0)
                final.append((fd, fv, fl))
            return final, new_count.reshape(1)

        def build_specs(template, spec):
            return jax.tree_util.tree_map(lambda _: spec, template)

        return jax.shard_map(per_device, mesh=ctx.mesh,
                         in_specs=(build_specs([tuple(c) for c in cols],
                                               P(axis)),
                                   P(axis), P(axis)),
                         out_specs=(build_specs([tuple(c) for c in cols],
                                                P(axis)), P(axis)),
                         check_vma=False)

    # memoized by (mesh, devices, bucket, schema shapes) in the shared
    # executable cache: a fresh jax.jit here re-traced the whole SPMD
    # shuffle program on EVERY collective exchange
    from spark_rapids_tpu.exec.stage_compiler import get_or_build
    prog = get_or_build("parallel.collective_shuffle", key, build)
    arrs = [tuple(c) for c in cols]
    out, new_counts = prog(arrs, counts, pids)
    return [tuple(o) for o in out], new_counts
