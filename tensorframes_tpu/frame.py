"""TensorFrame: a columnar, block-partitioned DataFrame for tensor compute.

TPU-native replacement for the reference's Spark DataFrame substrate. Where
the reference stored data as Spark `Row` objects and paid a boxed
row->NIO-buffer conversion on every task (`DataOps.scala:63-81`,
`datatypes.scala:114-127`), a TensorFrame stores each column as a dense
numpy array of shape ``(nrows, *cell_shape)`` — already in tensor layout, so
feeding a block to the accelerator is a zero-copy (or single-copy H2D) view.

Ragged columns (rows with varying cell shapes — the reference supports these
via per-row conversion, `TFDataOps.scala:90-103`) are stored as object
arrays of per-row numpy cells; `analyze` merges their shapes with
unknown-widening exactly like `ExperimentalOperations.scala:140-178`.

Partitioning: a frame carries block boundaries (`offsets`). A *block* plays
the role of a Spark partition: `map_blocks` applies the graph once per
block, and distributed execution shards blocks across the device mesh.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Union

import numpy as np

from .schema import ColumnInfo, FrameInfo, ScalarType, Shape, Unknown

__all__ = ["TensorFrame", "Column"]

ArrayLike = Union[np.ndarray, Sequence]


def _is_device_array(data) -> bool:
    import jax

    return isinstance(data, jax.Array)


class Column:
    """One column: dense array (lead dim = rows) or ragged object array.

    Dense values may be host numpy OR a `jax.Array` already resident in
    device HBM (possibly sharded over a mesh) — the north-star design:
    blocks live on the accelerator, and verbs keep them there
    (BASELINE.json: "converters bypass the JVM heap and write device
    buffers")."""

    def __init__(self, name: str, data: ArrayLike, dtype: Optional[ScalarType] = None):
        self.name = name
        self._host = None  # lazy host_values() cache for device columns
        if (
            isinstance(data, np.ndarray) and data.dtype != object
        ) or _is_device_array(data):
            self.values = data
            self.ragged: Optional[List[np.ndarray]] = None
            self.dtype = dtype or ScalarType.from_np_dtype(np.dtype(data.dtype))
            # Dense storage: the cell shape is fully known.
            self.cell_shape = Shape(data.shape[1:])
        else:
            # Bulk fast path: ONE np.asarray over the whole column beats
            # a million per-cell conversions (the reference's boxed
            # row-by-row copy loop was its recorded hot spot,
            # `DataOps.scala:63-81`; this is the columnar answer).
            # Truly ragged/string/object data falls through to the
            # per-cell path below.
            # list/tuple only: np.asarray over those always COPIES, so
            # the frame can never alias caller memory (a pandas Series
            # would share its buffer), and generators still reach the
            # consuming per-cell path below.
            bulk = None
            if (
                isinstance(data, (list, tuple))
                and len(data) > 0
                and dtype is not ScalarType.string
            ):
                try:
                    bulk = np.asarray(data)
                except (ValueError, TypeError):
                    bulk = None
            if (
                bulk is not None
                and bulk.dtype != object
                and bulk.dtype.kind not in ("U", "S")
            ):
                target = dtype or ScalarType.from_np_dtype(bulk.dtype)
                self.values = bulk.astype(target.np_dtype, copy=False)
                self.ragged = None
                self.dtype = target
                self.cell_shape = Shape(self.values.shape[1:])
                return
            cells = [np.asarray(x) for x in data]
            if dtype is None:
                if not cells:
                    raise ValueError(f"empty ragged column {name!r} needs a dtype")
                if cells[0].dtype.kind in ("U", "S", "O"):
                    dtype = ScalarType.string
                else:
                    dtype = ScalarType.from_np_dtype(
                        np.result_type(*[c.dtype for c in cells])
                    )
            self.dtype = dtype
            if dtype is not ScalarType.string:
                cells = [c.astype(dtype.np_dtype) for c in cells]
            self.ragged = cells
            # Without a scan we only know the rank (mirrors the reference:
            # an ArrayType column has shape [Unknown,...] until analyzed,
            # `ColumnInformation.scala:94-111`).
            rank = cells[0].ndim if cells else 0
            if any(c.ndim != rank for c in cells):
                raise ValueError(f"column {name!r}: rows disagree on rank")
            self.cell_shape = Shape((Unknown,) * rank)
            self.values = None  # type: ignore[assignment]
            self._try_densify()

    def _try_densify(self) -> None:
        """Promote a ragged column whose cells all share one shape to dense."""
        if self.ragged is None or self.dtype is ScalarType.string:
            return
        if not self.ragged:
            return
        s0 = self.ragged[0].shape
        if all(c.shape == s0 for c in self.ragged):
            self.values = np.stack(self.ragged) if s0 else np.asarray(
                [c[()] for c in self.ragged], dtype=self.dtype.np_dtype
            )
            self.values = self.values.astype(self.dtype.np_dtype)
            self.cell_shape = Shape(s0)
            self.ragged = None

    # ------------------------------------------------------------------
    @property
    def is_dense(self) -> bool:
        return self.ragged is None

    def __len__(self) -> int:
        return len(self.values) if self.is_dense else len(self.ragged)  # type: ignore[arg-type]

    @property
    def info(self) -> ColumnInfo:
        return ColumnInfo(self.name, self.dtype, self.cell_shape)

    def slice(self, start: int, stop: int) -> "Column":
        if self.is_dense:
            return Column(self.name, self.values[start:stop], self.dtype)
        return Column(self.name, self.ragged[start:stop], self.dtype)  # type: ignore[index]

    def row(self, i: int) -> np.ndarray:
        return self.values[i] if self.is_dense else self.ragged[i]  # type: ignore[index]

    def rows(self) -> Iterable[np.ndarray]:
        return iter(self.values) if self.is_dense else iter(self.ragged)  # type: ignore[arg-type]

    def host_values(self) -> np.ndarray:
        """One host numpy array of all cells — THE device->host boundary.

        Verbs keep dense columns device-resident end to end; this is the
        single explicit point where a column crosses to the host (group
        keys, pandas/Arrow export, user materialization). The copy is
        lazy and cached: the first call on a device column blocks on the
        async pipeline and pays one D2H transfer (counted in the
        ``host_sync`` profiling stat); later calls return the cached
        array. Host-numpy columns return their array as-is. Scalar
        string/object columns — which never densify because they cannot
        go to device — assemble an object vector (the reference grouped
        by ANY Catalyst column type, so string group keys must work)."""
        if self.is_dense:
            if isinstance(self.values, np.ndarray):
                return self.values
            if self._host is None:
                from .utils import telemetry as _tele
                from .utils.profiling import count

                count("host_sync")
                # host-sync leaf span: blocks on the async pipeline and
                # pays the D2H copy; attributed to the enclosing
                # dispatch's program when one is active
                with _tele.span(
                    "host_sync", kind="host_sync", column=self.name,
                    program=_tele.current_program(),
                ):
                    self._host = np.asarray(self.values)
                if _tele.enabled():
                    _tele.histogram_observe(
                        "d2h_bytes", float(self._host.nbytes)
                    )
            return self._host
        if not self.cell_shape.is_scalar:
            raise ValueError(
                f"column {self.name!r} is ragged; no single host array"
            )
        out = np.empty(len(self.ragged), dtype=object)  # type: ignore[arg-type]
        for i, c in enumerate(self.ragged):  # type: ignore[union-attr]
            out[i] = np.asarray(c)[()]  # ragged cells are 0-d ndarrays here
        return out

    def analyzed_cell_shape(self) -> Shape:
        """Scan all cells and merge shapes with unknown-widening
        (`ExperimentalOperations.scala:140-178`)."""
        if self.is_dense:
            return self.cell_shape
        merged: Optional[Shape] = None
        for c in self.ragged:  # type: ignore[union-attr]
            s = Shape(c.shape)
            if merged is None:
                merged = s
            else:
                m = merged.merge(s)
                if m is None:
                    raise ValueError(
                        f"column {self.name!r}: rows disagree on rank "
                        f"({merged} vs {s})"
                    )
                merged = m
        return merged if merged is not None else self.cell_shape

    def with_info(self, info: ColumnInfo) -> "Column":
        c = Column.__new__(Column)
        c.name = info.name
        c.values = self.values
        c.ragged = self.ragged
        c.dtype = info.dtype
        c.cell_shape = info.cell_shape
        c._host = self._host  # same buffer, so the host cache carries over
        return c


class TensorFrame:
    """Columnar, block-partitioned frame.

    ``offsets`` are block boundaries: block i covers rows
    ``offsets[i]:offsets[i+1]``. Blocks correspond to the reference's Spark
    partitions (each `map_blocks` graph application sees one block,
    `DebugRowOps.scala:384-398`).
    """

    def __init__(
        self,
        columns: Sequence[Column],
        offsets: Optional[Sequence[int]] = None,
    ):
        if not columns:
            raise ValueError("a TensorFrame needs at least one column")
        self._cols: Dict[str, Column] = {}
        n = len(columns[0])
        for c in columns:
            if len(c) != n:
                raise ValueError(
                    f"column {c.name!r} has {len(c)} rows, expected {n}"
                )
            if c.name in self._cols:
                raise ValueError(f"duplicate column {c.name!r}")
            self._cols[c.name] = c
        self.nrows = n
        if offsets is None:
            offsets = [0, n]
        offsets = list(offsets)
        if offsets[0] != 0 or offsets[-1] != n or any(
            offsets[i] > offsets[i + 1] for i in range(len(offsets) - 1)
        ):
            raise ValueError(f"bad block offsets {offsets} for {n} rows")
        self.offsets = offsets

    # ---- constructors --------------------------------------------------
    @classmethod
    def from_dict(
        cls,
        data: Dict[str, ArrayLike],
        num_blocks: Optional[int] = None,
        dtypes: Optional[Dict[str, ScalarType]] = None,
    ) -> "TensorFrame":
        cols = [
            Column(name, values, (dtypes or {}).get(name))
            for name, values in data.items()
        ]
        tf = cls(cols)
        if num_blocks is not None:
            tf = tf.repartition(num_blocks)
        return tf

    @classmethod
    def from_pandas(cls, pdf, num_blocks: Optional[int] = None) -> "TensorFrame":
        data = {}
        for name in pdf.columns:
            series = pdf[name]
            if series.dtype == object:
                data[name] = list(series)
            else:
                data[name] = series.to_numpy()
        return cls.from_dict(data, num_blocks=num_blocks)

    @classmethod
    def from_arrow(cls, table, num_blocks: Optional[int] = None) -> "TensorFrame":
        """Build from a pyarrow Table: primitive columns become dense,
        fixed-size-list columns dense vectors, list columns ragged. This
        is the interchange path for Spark-style ingestion (Arrow IPC from
        executor partitions; SURVEY.md §7.7's bridge)."""
        import pyarrow as pa

        data: Dict[str, ArrayLike] = {}
        for name in table.column_names:
            col = table.column(name).combine_chunks()
            if pa.types.is_fixed_size_list(col.type):
                width = col.type.list_size
                flat = col.values.to_numpy(zero_copy_only=False)
                data[name] = flat.reshape(-1, width)
            elif pa.types.is_list(col.type) or pa.types.is_large_list(col.type):
                data[name] = [
                    np.asarray(x) for x in col.to_pylist()
                ]
            else:
                data[name] = col.to_numpy(zero_copy_only=False)
        return cls.from_dict(data, num_blocks=num_blocks)

    def to_arrow(self):
        """Export to a pyarrow Table (dense vectors as fixed-size lists,
        ragged as lists)."""
        import pyarrow as pa

        arrays = []
        names = []
        for name in self.columns:
            c = self.column(name)
            names.append(name)
            if c.is_dense and c.cell_shape.is_scalar:
                arrays.append(pa.array(np.asarray(c.values)))
            elif c.is_dense and c.cell_shape.rank == 1:
                vals = np.asarray(c.values)
                width = vals.shape[1]
                arrays.append(
                    pa.FixedSizeListArray.from_arrays(
                        pa.array(vals.ravel()), width
                    )
                )
            else:
                arrays.append(
                    pa.array([np.asarray(r).tolist() for r in c.rows()])
                )
        return pa.table(dict(zip(names, arrays)))

    def pad_ragged(self, col_name: str, length_col: Optional[str] = None) -> "TensorFrame":
        """Materialize a ragged rank-1 column as a zero-padded dense column
        plus a length column — the masked-execution bridge for block-level
        ops over variable-length rows (the reference ran these per-row,
        `TFDataOps.scala:90-103`; padding + masks is the XLA-native way).
        Uses the native pack kernel when built."""
        c = self.column(col_name)
        if c.is_dense:
            return self
        if c.cell_shape.rank != 1:
            raise ValueError("pad_ragged supports rank-1 ragged columns")
        from .native import pack_ragged

        cells = [np.asarray(r) for r in c.rows()]
        packed = pack_ragged(cells)
        if packed is None:  # pure-python fallback
            max_len = max(x.size for x in cells)
            out = np.zeros((len(cells), max_len), dtype=cells[0].dtype)
            lens = np.empty(len(cells), np.int32)
            for i, x in enumerate(cells):
                out[i, : x.size] = x
                lens[i] = x.size
        else:
            out, lens = packed
        new_cols = [
            Column(col_name, out, c.dtype),
            Column(length_col or f"{col_name}_len", lens),
        ]
        return self.with_columns(new_cols)

    @classmethod
    def from_rows(
        cls,
        rows: Sequence[Dict[str, ArrayLike]],
        num_blocks: Optional[int] = None,
    ) -> "TensorFrame":
        if not rows:
            raise ValueError("from_rows needs at least one row")
        names = list(rows[0].keys())
        data = {n: [r[n] for r in rows] for n in names}
        return cls.from_dict(data, num_blocks=num_blocks)

    # ---- basic accessors ----------------------------------------------
    @property
    def columns(self) -> List[str]:
        return list(self._cols.keys())

    @property
    def info(self) -> FrameInfo:
        return FrameInfo([c.info for c in self._cols.values()])

    def column(self, name: str) -> Column:
        if name not in self._cols:
            raise KeyError(
                f"no column {name!r}; available: {self.columns}"
            )
        return self._cols[name]

    def __getitem__(self, name: str) -> Column:
        return self.column(name)

    @property
    def num_blocks(self) -> int:
        return len(self.offsets) - 1

    def block_sizes(self) -> List[int]:
        return [
            self.offsets[i + 1] - self.offsets[i]
            for i in range(self.num_blocks)
        ]

    def bucketed_block_sizes(self) -> List[int]:
        """The block-lead shapes dispatch will actually compile for this
        frame under the current shape policy: the bucket-ladder rung per
        block (`shape_policy.bucket_for`) with ``config.shape_bucketing``
        on, the raw `block_sizes` with it off — so ``len(set(...))`` is
        an honest compiled-shape budget either way (the introspection
        surface the bucketing tests assert against). Empty blocks map
        to 0 (never dispatched).
        Per-dispatch eligibility (non-row-local maps, unclassified
        reduces) can still keep individual programs on the raw sizes."""
        from . import config as _config
        from .shape_policy import bucket_for

        if not _config.get().shape_bucketing:
            return self.block_sizes()
        return [bucket_for(n) for n in self.block_sizes()]

    def block(self, i: int) -> "TensorFrame":
        lo, hi = self.offsets[i], self.offsets[i + 1]
        return TensorFrame([c.slice(lo, hi) for c in self._cols.values()])

    def blocks(self) -> Iterable["TensorFrame"]:
        for i in range(self.num_blocks):
            yield self.block(i)

    # ---- restructuring -------------------------------------------------
    def repartition(self, num_blocks: int) -> "TensorFrame":
        """Split into ``num_blocks`` near-equal blocks (like df.repartition)."""
        if num_blocks < 1:
            raise ValueError("num_blocks must be >= 1")
        edges = np.linspace(0, self.nrows, num_blocks + 1).astype(int)
        return TensorFrame(list(self._cols.values()), list(edges))

    def select(self, names: Sequence[str]) -> "TensorFrame":
        return TensorFrame([self.column(n) for n in names], self.offsets)

    def with_columns(self, cols: Sequence[Column]) -> "TensorFrame":
        merged = dict(self._cols)
        for c in cols:
            merged[c.name] = c
        return TensorFrame(list(merged.values()), self.offsets)

    # ---- schema ops (analyze / append_shape) ---------------------------
    def analyze(self) -> "TensorFrame":
        """Scan data, refine every column's cell shape
        (`ExperimentalOperations.analyze`, `ExperimentalOperations.scala:39-51`)."""
        new_cols = []
        for c in self._cols.values():
            info = ColumnInfo(c.name, c.dtype, c.analyzed_cell_shape())
            new_cols.append(c.with_info(info))
        return TensorFrame(new_cols, self.offsets)

    def append_shape(self, name: str, cell_shape: Shape) -> "TensorFrame":
        """Manually attach a cell shape (`ExperimentalOperations.scala:53-68`)."""
        c = self.column(name)
        info = ColumnInfo(name, c.dtype, cell_shape)
        cols = [
            c.with_info(info) if cn == name else col
            for cn, col in self._cols.items()
        ]
        return TensorFrame(cols, self.offsets)

    # ---- device placement ----------------------------------------------
    def to_device(self, mesh=None, device=None) -> "TensorFrame":
        """Move dense columns into device HBM (sharded over the mesh's
        ``data`` axis when a mesh is given; committed onto ``device``
        when one is given — the block scheduler's streaming prefetch
        targets each chunk's assigned device this way). Ragged/string
        columns stay on host. Verb outputs on a device-resident frame
        stay on device — host materialization happens only at
        `to_pandas`/`collect`."""
        import jax

        from .utils import telemetry as _tele

        if mesh is not None and device is not None:
            raise ValueError(
                "to_device: mesh= and device= are mutually exclusive"
            )
        h2d_bytes = 0
        new_cols = []
        # transfer span: the H2D issue window (device_put is async — the
        # copy itself overlaps downstream compute; this measures what the
        # caller's thread paid to start it)
        with _tele.span("to_device", kind="transfer"):
            for c in self._cols.values():
                if c.is_dense and c.dtype is not ScalarType.string:
                    # shard_to_mesh splits the lead dim over the 'data'
                    # axis only
                    if (
                        mesh is not None
                        and "data" in mesh.shape
                        and len(c) % mesh.shape["data"] == 0
                    ):
                        from .parallel.mesh import shard_to_mesh

                        host = np.asarray(c.values)
                        h2d_bytes += host.nbytes
                        vals = shard_to_mesh(mesh, host)
                    elif (
                        isinstance(c.values, jax.Array)
                        and mesh is None
                        and device is None
                    ):
                        # already device-resident: a device_put here would
                        # round-trip D2H (np.asarray blocks) then re-upload
                        new_cols.append(c)
                        continue
                    elif (
                        isinstance(c.values, jax.Array) and device is not None
                    ):
                        # device->device commit/move: async, never via host
                        vals = jax.device_put(c.values, device)
                    else:
                        host = np.asarray(c.values)
                        h2d_bytes += host.nbytes
                        vals = jax.device_put(host, device)
                    nc = Column(c.name, vals, c.dtype)
                    nc.cell_shape = c.cell_shape
                    new_cols.append(nc)
                else:
                    new_cols.append(c)
        if h2d_bytes and _tele.enabled():
            _tele.histogram_observe("h2d_bytes", float(h2d_bytes))
        return TensorFrame(new_cols, self.offsets)

    def to_global(self, mesh=None) -> "GlobalFrame":  # noqa: F821
        """Shard this frame's dense columns into single `jax.Array`s
        over a data mesh (`globalframe.GlobalFrame`): every verb on the
        result compiles to ONE SPMD program spanning all devices —
        maps run shard-local, classified reduces lower to in-program
        collectives. ``mesh`` defaults to a 1-D data mesh over every
        healthy local device. `GlobalFrame.collect()` is the inverse
        boundary (slices the sharded pad rows back off)."""
        from .globalframe import GlobalFrame

        return GlobalFrame.from_frame(self, mesh=mesh)

    # ---- lazy plans ----------------------------------------------------
    def lazy(self) -> "LazyFrame":  # noqa: F821 — forward ref, see lazy.py
        """Wrap this frame into a `LazyFrame`: subsequent graph-based
        ``map_blocks`` calls defer and fuse into one XLA program per
        block, executed at the first terminal action (`collect` /
        `host_values` / any reduce/aggregate / `.force()`). See
        `tensorframes_tpu.lazy`."""
        from .lazy import LazyFrame

        return LazyFrame(self)

    # ---- export --------------------------------------------------------
    def host_values(self, name: str) -> np.ndarray:
        """Host numpy array of one column — `Column.host_values` through
        the frame: the explicit, cached device->host boundary."""
        return self.column(name).host_values()

    def to_host(self) -> "TensorFrame":
        """Materialize every device-resident column to host numpy (one
        cached D2H copy per column; `to_device`'s inverse). The frame's
        verbs never call this — chained verbs stay on device until the
        USER crosses the boundary here or via `host_values`/`to_pandas`/
        `collect`."""
        new_cols = []
        for c in self._cols.values():
            if c.is_dense and not isinstance(c.values, np.ndarray):
                nc = Column(c.name, c.host_values(), c.dtype)
                nc.cell_shape = c.cell_shape
                new_cols.append(nc)
            else:
                new_cols.append(c)
        return TensorFrame(new_cols, self.offsets)

    def to_pandas(self):
        import pandas as pd

        data = {}
        for c in self._cols.values():
            if c.is_dense and c.cell_shape.is_scalar:
                data[c.name] = c.host_values()
            elif c.is_dense:
                # one cached D2H copy, then host-side row iteration (a
                # device column would sync once per row otherwise)
                data[c.name] = [r.tolist() for r in c.host_values()]
            else:
                data[c.name] = [np.asarray(r).tolist() for r in c.rows()]
        return pd.DataFrame(data)

    def collect(self) -> List[Dict[str, np.ndarray]]:
        # Materialize each dense column once through the cached
        # host_values boundary (a device column would otherwise pay one
        # device->host sync per cell).
        host: Dict[str, Column] = {}
        for n, c in self._cols.items():
            if c.is_dense and not isinstance(c.values, np.ndarray):
                host[n] = Column(n, c.host_values(), c.dtype)
            else:
                host[n] = c
        names = self.columns
        # zip over the arrays directly: C-level row iteration instead of
        # a Python row(i) call per cell
        col_iters = [host[n].rows() for n in names]
        return [dict(zip(names, vals)) for vals in zip(*col_iters)]

    def print_schema(self) -> None:
        print(self.info.explain())

    def __repr__(self) -> str:
        return (
            f"TensorFrame[{self.nrows} rows x {len(self._cols)} cols, "
            f"{self.num_blocks} blocks]({', '.join(map(repr, self.info))})"
        )


def factorize_keys(key_names, key_arrays):
    """Factorize one or more group-key columns into
    (key_out: name -> unique values aligned per group, inverse: row -> gid).

    Multi-key tuples combine per-key codes mixed-radix into one int64 per
    row (np.unique cannot handle 2-D object arrays), the host-side
    analogue of the Catalyst shuffle key (`DebugRowOps.scala:554-599`).
    """
    if len(key_arrays) == 1:
        arr = np.asarray(key_arrays[0])
        try:
            import pandas as pd

            # hash-based O(n) — np.unique's sort dominated keyed
            # aggregation wall time at the 10M-row benchmark scale.
            # sort=True keeps np.unique's sorted-key output contract;
            # use_na_sentinel=False keeps NaN as a real key like
            # np.unique does.
            inverse, uniq = pd.factorize(
                arr, sort=True, use_na_sentinel=False
            )
            return {key_names[0]: np.asarray(uniq)}, inverse.astype(np.int64)
        except (ImportError, TypeError):
            uniq, inverse = np.unique(arr, return_inverse=True)
            return {key_names[0]: uniq}, inverse
    per_key = [np.unique(a, return_inverse=True) for a in key_arrays]
    combo = np.zeros(len(key_arrays[0]), np.int64)
    for u, inv in per_key:
        radix = max(len(u), 1)
        if combo.max(initial=0) > (2**62) // radix:
            raise ValueError(
                "aggregate: combined group-key cardinality overflows"
            )
        combo = combo * radix + inv
    _, first_idx, inverse = np.unique(
        combo, return_index=True, return_inverse=True
    )
    key_out = {
        k: np.asarray(key_arrays[i])[first_idx]
        for i, k in enumerate(key_names)
    }
    return key_out, inverse
