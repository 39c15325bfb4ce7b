"""Columnar batches (device Table / host RecordBatch equivalents).

Reference counterparts: Spark's ``ColumnarBatch`` + cuDF ``Table`` interop in
GpuColumnVector.java (from(Table), from(ColumnarBatch)), and host-side
``RapidsHostColumnVector`` batches.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, List, Optional, Sequence

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.column import (DeviceColumn, HostColumn,
                                              bucket_rows)


@dataclasses.dataclass
class ColumnarBatch:
    """A device-resident batch: list of DeviceColumns + logical row count.

    All columns share the same bucket (padded leading dim), so a whole batch
    feeds a single jit'ed XLA program with static shapes.
    """

    columns: List[DeviceColumn]
    row_count: int
    names: Optional[List[str]] = None

    def __post_init__(self):
        from spark_rapids_tpu.columnar.column import DeferredCount
        deferred = isinstance(self.row_count, DeferredCount)
        for c in self.columns:
            if deferred or isinstance(c.row_count, DeferredCount):
                # identity check only — never force a device sync here
                if c.row_count is not self.row_count:
                    raise ValueError(
                        "deferred-count batch requires every column to "
                        "share the batch's count object")
            elif c.row_count != self.row_count:
                raise ValueError(
                    f"column rows {c.row_count} != batch rows {self.row_count}")
        if self.columns:
            b0 = self.columns[0].bucket
            for c in self.columns[1:]:
                if c.bucket != b0:
                    raise ValueError(
                        f"mixed buckets in batch: {c.bucket} != {b0} "
                        "(all columns must share one padded shape)")

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    @property
    def schema(self) -> T.StructType:
        names = self.names or [f"c{i}" for i in range(len(self.columns))]
        return T.StructType([T.StructField(n, c.data_type)
                             for n, c in zip(names, self.columns)])

    @property
    def bucket(self) -> int:
        if not self.columns:
            return bucket_rows(self.row_count)
        return self.columns[0].bucket

    def column(self, i: int) -> DeviceColumn:
        return self.columns[i]

    def nbytes(self) -> int:
        return sum(c.nbytes() for c in self.columns)

    def sized_nbytes(self) -> int:
        """Unpadded logical size estimate (planner/coalesce sizing).

        A deferred row count is NOT forced here (spill registration sits on
        the hot path and would pay a host sync per batch);
        the padded size is returned instead — conservative, and truthful
        about what HBM actually holds."""
        if self.bucket == 0:
            return 0
        from spark_rapids_tpu.columnar.column import DeferredCount
        rc = self.row_count
        if isinstance(rc, DeferredCount) and not rc.is_forced:
            return self.nbytes()
        return int(self.nbytes() * (int(rc) / max(self.bucket, 1)))

    def to_host(self, spec_rows=None) -> "HostColumnarBatch":
        from spark_rapids_tpu.columnar.transfer import download_host_batch
        return download_host_batch(self, spec_rows=spec_rows)

    def select(self, indices: Sequence[int]) -> "ColumnarBatch":
        names = None if self.names is None else [self.names[i] for i in indices]
        return ColumnarBatch([self.columns[i] for i in indices],
                             self.row_count, names)

    def __repr__(self):
        return (f"ColumnarBatch(rows={self.row_count}, "
                f"cols=[{', '.join(str(c.data_type) for c in self.columns)}])")


@dataclasses.dataclass
class HostColumnarBatch:
    """Host-resident batch over Arrow arrays (wire/spill/CPU-exec form)."""

    columns: List[HostColumn]
    row_count: int
    names: Optional[List[str]] = None

    def __post_init__(self):
        for c in self.columns:
            if len(c) != self.row_count:
                raise ValueError(
                    f"ragged batch: column has {len(c)} rows, batch has "
                    f"{self.row_count}")

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    @property
    def schema(self) -> T.StructType:
        names = self.names or [f"c{i}" for i in range(len(self.columns))]
        return T.StructType([T.StructField(n, c.data_type)
                             for n, c in zip(names, self.columns)])

    def to_device(self, bucket: Optional[int] = None) -> ColumnarBatch:
        from spark_rapids_tpu.columnar.transfer import upload_host_batch
        return upload_host_batch(self, bucket)

    def to_arrow(self):
        import pyarrow as pa
        names = self.names or [f"c{i}" for i in range(len(self.columns))]
        return pa.record_batch([c.arrow for c in self.columns], names=names)

    def nbytes(self) -> int:
        return sum(c.nbytes() for c in self.columns)

    def slice(self, offset: int, length: int) -> "HostColumnarBatch":
        return HostColumnarBatch([c.slice(offset, length) for c in self.columns],
                                 length, self.names)

    def to_pydict(self):
        names = self.names or [f"c{i}" for i in range(len(self.columns))]
        return {n: c.to_pylist() for n, c in zip(names, self.columns)}

    def __repr__(self):
        return (f"HostColumnarBatch(rows={self.row_count}, "
                f"cols=[{', '.join(str(c.data_type) for c in self.columns)}])")


def batch_from_arrow(rb) -> HostColumnarBatch:
    """From a pyarrow RecordBatch or Table."""
    import pyarrow as pa
    if isinstance(rb, pa.Table):
        rb = rb.combine_chunks()
        cols = [HostColumn(rb.column(i)) for i in range(rb.num_columns)]
        return HostColumnarBatch(cols, rb.num_rows, list(rb.column_names))
    cols = [HostColumn(rb.column(i)) for i in range(rb.num_columns)]
    return HostColumnarBatch(cols, rb.num_rows, list(rb.schema.names))


def batch_to_arrow(batch) -> "object":
    if isinstance(batch, ColumnarBatch):
        batch = batch.to_host()
    return batch.to_arrow()


def batch_from_pydict(d, schema: Optional[T.StructType] = None) -> HostColumnarBatch:
    cols = []
    names = []
    n = None
    for name, values in d.items():
        dt = None
        if schema is not None:
            dt = schema.types[schema.field_index(name)]  # match by name
        if isinstance(values, np.ndarray):
            col = HostColumn.from_numpy(values, data_type=dt)
        else:
            col = HostColumn.from_pylist(list(values), dt)
        if n is None:
            n = len(col)
        cols.append(col)
        names.append(name)
    return HostColumnarBatch(cols, n or 0, names)


def concat_host_batches(batches: Iterable[HostColumnarBatch]) -> HostColumnarBatch:
    import pyarrow as pa
    batches = list(batches)
    assert batches, "cannot concat zero batches"
    # a column may arrive dictionary-encoded from one source and plain
    # from another (encoded scan vs adapted/evolved file): arrow refuses
    # mixed concat, so decode the minority form per column (all-encoded
    # columns concat encoded — arrow unifies the dictionaries)
    if len(batches) > 1 and any(c.is_dict_encoded
                                for b in batches for c in b.columns):
        mixed = [ci for ci in range(min(b.num_columns for b in batches))
                 if len({b.columns[ci].is_dict_encoded
                         for b in batches}) > 1]
        if mixed:
            from spark_rapids_tpu.columnar.encoding import host_decoded
            fixed = []
            for b in batches:
                cols = list(b.columns)
                for ci in mixed:
                    c = cols[ci]
                    if c.is_dict_encoded:
                        cols[ci] = HostColumn(host_decoded(c.arrow),
                                              c.data_type)
                fixed.append(HostColumnarBatch(cols, b.row_count,
                                               b.names))
            batches = fixed
    tables = [pa.Table.from_batches([b.to_arrow()]) for b in batches]
    return batch_from_arrow(pa.concat_tables(tables).combine_chunks())
