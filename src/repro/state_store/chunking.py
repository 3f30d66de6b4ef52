"""Pytree <-> logical record chunking.

Training state (params + optimizer) becomes a set of *logical records*:
    table = "state",  key = "<pytree/path>#<chunk_idx>"
Each record holds ``chunk_elems`` raw elements of one leaf array.  Keys are
purely logical — which page a chunk lands on is the DC's business — which is
exactly what lets the same log restore onto a DC with a different page size
or shard layout (the paper's replica argument, Section 1.1).
"""
from __future__ import annotations

import struct
from typing import Any, Iterator

import jax
import numpy as np

from repro.obs.trace import TRACER

CHUNK_ELEMS = 16_384          # elements per record (~64 KiB fp32)
_HDR = struct.Struct("<II")   # dtype code, n elements
_DTYPES = ["float32", "bfloat16", "float16", "int32", "int64", "uint32",
           "float64", "int8", "uint8", "bool"]


def _leaf_paths(tree: Any) -> list[tuple[str, Any]]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = []
    for path, leaf in flat:
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        out.append((name, leaf))
    return out


def encode_chunk(arr_bytes: bytes | memoryview, dtype: str, n: int) -> bytes:
    """Header + the elements' raw bytes, in one copy of ``arr_bytes``
    (any contiguous buffer)."""
    return _HDR.pack(_DTYPES.index(dtype), n) + arr_bytes


def decode_chunk(raw: bytes) -> tuple[np.ndarray, str]:
    code, n = _HDR.unpack_from(raw, 0)
    dtype = _DTYPES[code]
    np_dtype = np.uint16 if dtype == "bfloat16" else np.dtype(dtype)
    arr = np.frombuffer(raw, dtype=np_dtype, offset=_HDR.size, count=n)
    return arr, dtype


def start_host_copies(tree: Any) -> int:
    """Start the device->host copy of every leaf that has one (a
    ``jax.Array``), in leaf order, and return how many were started.

    Nothing waits: a later ``np.asarray(leaf)`` waits only for its own
    copy, so the copies of later leaves land while earlier ones are
    consumed.  Other leaves (numpy arrays, Python scalars) are left as
    they are; a leaf whose copy is already under way returns at once."""
    n = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        copy = getattr(leaf, "copy_to_host_async", None)
        if copy is not None:
            copy()
            n += 1
    return n


def tree_to_records(tree: Any, chunk_elems: int = CHUNK_ELEMS
                    ) -> Iterator[tuple[bytes, bytes]]:
    """Yield (key, value) records for every chunk of every leaf.

    Every leaf's copy to the host is started before the first record
    (``start_host_copies``).  Spans per leaf (``TrainWAL.log_state``
    saves through here): ``wal.save.pull``, the wait for the leaf's copy,
    then ``wal.save.records``, its chunks.  The latter stays open while
    the caller consumes them, so it times the caller's work on each chunk
    too."""
    start_host_copies(tree)
    for name, leaf in _leaf_paths(tree):
        with TRACER.span("wal.save.pull"):
            arr = np.asarray(leaf)
        dtype = str(leaf.dtype)
        view = (arr.view(np.uint16) if dtype == "bfloat16" else arr).reshape(-1)
        n = view.size
        n_chunks = max(1, (n + chunk_elems - 1) // chunk_elems)
        with TRACER.span("wal.save.records"):
            for c in range(n_chunks):
                part = view[c * chunk_elems:(c + 1) * chunk_elems]
                key = f"{name}#{c:06d}".encode()
                yield key, encode_chunk(memoryview(part), dtype, part.size)


def records_to_tree(template: Any, records: dict[bytes, bytes],
                    chunk_elems: int = CHUNK_ELEMS) -> Any:
    """Rebuild a pytree shaped like ``template`` from chunk records.

    Spans per leaf (``TrainWAL.restore`` rebuilds through here):
    ``wal.restore.decode``, its chunks decoded and joined on the host,
    then ``wal.restore.put``, the leaf put on the device; one leaf at a
    time is held decoded."""
    leaves = []
    for name, leaf in _leaf_paths(template):
        shape = leaf.shape
        dtype = str(leaf.dtype)
        n = int(np.prod(shape)) if shape else 1
        n_chunks = max(1, (n + chunk_elems - 1) // chunk_elems)
        with TRACER.span("wal.restore.decode"):
            parts = []
            for c in range(n_chunks):
                key = f"{name}#{c:06d}".encode()
                raw = records.get(key)
                if raw is None:
                    raise KeyError(f"missing state chunk {key!r}")
                arr, _ = decode_chunk(raw)
                parts.append(arr)
            flat = np.concatenate(parts) if len(parts) > 1 else parts[0]
        with TRACER.span("wal.restore.put"):
            if dtype == "bfloat16":
                out = jax.numpy.asarray(flat.view(jax.numpy.bfloat16)
                                        ).reshape(shape)
            else:
                out = jax.numpy.asarray(flat.reshape(shape))
        leaves.append(out)
    treedef = jax.tree_util.tree_structure(template)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def n_state_records(tree: Any, chunk_elems: int = CHUNK_ELEMS) -> int:
    total = 0
    for _, leaf in _leaf_paths(tree):
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        total += max(1, (n + chunk_elems - 1) // chunk_elems)
    return total
