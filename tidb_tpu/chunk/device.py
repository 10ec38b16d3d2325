"""Device-resident columnar batches — the HBM representation.

Design (SURVEY.md §7 layer 1): static shapes everywhere. A region batch is
padded to a fixed capacity and carries a `row_valid` mask; NULLs are a
separate per-column mask. XLA then sees one shape per (schema, capacity)
pair and compiles one fused program per DAG fingerprint.

Type mapping onto device dtypes:

  int / uint       int64  (uint64 bit-cast; unsigned compare via sign-flip)
  double / float   float64 / float32
  decimal(p,s)     int64 scaled by 10^s  — exact, VPU-friendly
  datetime/date    int64  (order-preserving packed layout, types/mytime.py)
  duration         int64 nanoseconds
  string/bytes     uint8 [N, W] padded + int32 lengths; W static per batch.
                   Lexicographic compare/sort/group uses big-endian packed
                   int64 words (pack_string_words) so strings become a small
                   tuple of sortable int64 columns.

Reference seam: these batches are what the unistore coprocessor decodes rows
into (ref: cophandler/mpp_exec.go:110-244 tableScanExec -> chunk.Chunk); we
decode straight to numpy then ship whole columns to HBM in one transfer.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..types import FieldType, TypeCode
from .chunk import Chunk
from .column import Column, numpy_dtype_for

# max packed words used for on-device string compare/group keys (8 bytes each)
STRING_WORDS = 4


@jax.tree_util.register_pytree_node_class
@dataclass
class DeviceColumn:
    """One column on device. `data` is [N] for fixed-width, [N, W] for varlen."""

    data: jax.Array
    null: jax.Array  # bool [N]; True = NULL
    length: jax.Array | None  # int32 [N] for varlen, else None
    ft: FieldType  # static

    def tree_flatten(self):
        children = (self.data, self.null, self.length)
        return children, self.ft

    @classmethod
    def tree_unflatten(cls, ft, children):
        return cls(children[0], children[1], children[2], ft)

    def is_varlen(self) -> bool:
        return self.data.ndim == 2

    @property
    def capacity(self) -> int:
        return self.data.shape[0]


@jax.tree_util.register_pytree_node_class
@dataclass
class DeviceBatch:
    """A capacity-padded batch of rows on device."""

    cols: list[DeviceColumn]
    row_valid: jax.Array  # bool [N]; False = padding
    n_rows: jax.Array  # int32 scalar (actual row count)

    def tree_flatten(self):
        return (self.cols, self.row_valid, self.n_rows), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def capacity(self) -> int:
        return self.row_valid.shape[0]

    def nbytes(self) -> int:
        """Device bytes held by this batch: every leaf, masks and lengths
        included (what the device-byte gauges count)."""
        return sum(x.nbytes for x in jax.tree_util.tree_leaves(self))


def _pad(arr: np.ndarray, capacity: int, fill=0) -> np.ndarray:
    n = len(arr)
    if n == capacity:
        return arr
    out = np.full((capacity,) + arr.shape[1:], fill, arr.dtype)
    out[:n] = arr
    return out


def host_column_arrays(col: Column, capacity: int, str_width: int | None = None):
    """Column -> (data, null, length|None) numpy arrays padded to capacity."""
    n = len(col)
    null = _pad(col.null.astype(bool), capacity, True)
    if not col.is_varlen():
        data = col.data
        if data.dtype == np.uint64:
            data = data.view(np.int64)
        return _pad(data, capacity), null, None
    lens = (col.offsets[1:] - col.offsets[:-1]).astype(np.int32)
    max_len = int(lens.max()) if n else 0
    w = int(str_width) if str_width else max(1, max_len)
    if max_len > w:
        raise ValueError(f"varlen column has a {max_len}-byte value but str_width={w}")
    data = np.zeros((capacity, w), np.uint8)
    for i in range(n):
        ln = min(int(lens[i]), w)
        data[i, :ln] = col.blob[col.offsets[i]: col.offsets[i] + ln]
    return data, null, _pad(lens, capacity)


def _put(host: "DeviceBatch", sharding) -> "DeviceBatch":
    """A batch of host arrays onto the device: leaf by leaf onto the default
    device, or, given a `sharding`, the whole tree in one `jax.device_put`,
    each device receiving its own part from the host."""
    if sharding is None:
        return jax.tree.map(jnp.asarray, host)
    return jax.device_put(host, sharding)


def to_device_batch(chunk: Chunk, capacity: int | None = None, str_widths: dict[int, int] | None = None,
                    sharding=None) -> DeviceBatch:
    n = chunk.num_rows()
    cap = capacity or max(1, n)
    cols = []
    for ci, col in enumerate(chunk.columns):
        _check_ci_ascii(col)
        w = (str_widths or {}).get(ci)
        cols.append(DeviceColumn(*host_column_arrays(col, cap, w), col.ft))
    row_valid = np.zeros(cap, bool)
    row_valid[:n] = True
    return _put(DeviceBatch(cols, row_valid, np.int32(n)), sharding)


def shared_str_widths(chunks: list[Chunk]) -> dict[int, int]:
    """Per-column max byte width across a batch of same-schema chunks — the
    shared varlen layout a region-stacked batch must agree on (each region's
    own max would give ragged [N, W] planes that cannot stack)."""
    widths: dict[int, int] = {}
    for ch in chunks:
        for ci, col in enumerate(ch.columns):
            if not col.is_varlen():
                continue
            w = 1
            if len(col):
                w = max(int((col.offsets[1:] - col.offsets[:-1]).max()), 1)
            widths[ci] = max(widths.get(ci, 1), w)
    return widths


def _check_ci_ascii(col: Column) -> None:
    """The device CI kernels fold ASCII only; any non-ASCII byte in a
    case/accent-insensitive column routes the whole plan to the
    weight-based oracle (executor.py's NotImplementedError fallback)
    rather than comparing wrongly (VERDICT r4 weak #6). THE one routing
    check — both the single-region and the stacked batch builders call
    it, so batched and per-region dispatch can never route differently."""
    if col.ft.is_string() and col.ft.is_ci() and col.is_varlen() and len(col):
        if col.blob is not None and col.blob.size and int(col.blob.max()) >= 0x80:
            raise NotImplementedError(
                "non-ASCII data under a CI collation is oracle-evaluated"
            )


def to_stacked_device_batch(chunks: list[Chunk], capacity: int, sharding=None) -> DeviceBatch:
    """Stack same-schema chunks into ONE region-batched DeviceBatch whose
    every leaf carries a leading region axis: data [B, cap, ...], null/
    row_valid [B, cap], n_rows [B]. This is the input shape of the vmapped
    fused program (the batch-coprocessor analog of stacking per-region
    fragments for one launch); `jax.vmap(program, in_axes=0)` maps each
    region lane back to the single-region program unchanged.

    All chunks must share a schema; varlen columns are padded to the
    batch-wide max width (shared_str_widths). Stacking happens host-side so
    the whole batch ships to HBM in one transfer per column, or, given a
    `sharding` (the mesh tier's, over the region axis), in one put that
    hands each device its own lanes."""
    assert chunks, "cannot stack an empty region batch"
    widths = shared_str_widths(chunks)
    n_cols = chunks[0].num_cols()
    cols: list[DeviceColumn] = []
    for ci in range(n_cols):
        datas, nulls, lengths = [], [], []
        for ch in chunks:
            col = ch.columns[ci]
            _check_ci_ascii(col)
            data, null, length = host_column_arrays(col, capacity, widths.get(ci))
            datas.append(data)
            nulls.append(null)
            lengths.append(length)
        ft = chunks[0].columns[ci].ft
        has_len = lengths[0] is not None
        cols.append(DeviceColumn(np.stack(datas), np.stack(nulls), np.stack(lengths) if has_len else None, ft))
    row_valid = np.zeros((len(chunks), capacity), bool)
    for b, ch in enumerate(chunks):
        row_valid[b, : ch.num_rows()] = True
    n_rows = np.array([ch.num_rows() for ch in chunks], np.int32)
    return _put(DeviceBatch(cols, row_valid, n_rows), sharding)


def pack_string_words(data: jax.Array, length: jax.Array, n_words: int = STRING_WORDS) -> jax.Array:
    """[N, W] uint8 + lengths -> [N, n_words + 1] int64, big-endian packed.

    Bytes beyond each row's length are zeroed and the byte length is appended
    as a final tiebreaker word, so comparing rows as tuples of these words ==
    bytes.Compare on the originals truncated to 8*n_words bytes (the length
    word distinguishes b"a" from b"a\\x00", which zero-padding alone cannot).
    Strings differing only beyond 8*n_words bytes still tie — callers that
    need exact semantics on longer strings must fall back to the host path.
    """
    nbytes = n_words * 8
    w = data.shape[1]
    if w < nbytes:
        data = jnp.pad(data, ((0, 0), (0, nbytes - w)))
    else:
        data = data[:, :nbytes]
    pos = jnp.arange(nbytes, dtype=jnp.int32)
    data = jnp.where(pos[None, :] < length[:, None], data, 0)
    words = data.reshape(data.shape[0], n_words, 8).astype(jnp.int64)
    shifts = jnp.array([56, 48, 40, 32, 24, 16, 8, 0], jnp.int64)
    packed = (words << shifts[None, None, :]).sum(axis=-1)
    # flip sign bit so unsigned byte order == signed int64 order
    packed = packed ^ jnp.int64(-0x8000000000000000)
    return jnp.concatenate([packed, length[:, None].astype(jnp.int64)], axis=1)


def device_dtype_for(ft: FieldType):
    dt = numpy_dtype_for(ft)
    if dt is None:
        return jnp.uint8
    if dt == np.uint64:
        return jnp.int64
    return jnp.dtype(dt)
