"""Versioned, checksummed snapshot format with atomic rename-commit.

One snapshot file holds one Python object graph — pytrees of jax/numpy
arrays (any dtype, including the coded store's bf16 slices), containers
with non-string keys, coding keys (``CodingScheme``), re-assembly specs
(``StackedRowSpec`` and raw ``(treedef, shapes)`` pairs), ``StoreStats``,
and ``StagePlan``.  The encoding is exact: array payloads are raw bytes
(dtype/shape preserved bit-for-bit, never promoted), scalars ride in the
JSON header (Python's json round-trips finite floats exactly via repr).

File layout::

    MAGIC "REPROSN1" | u32 version | u64 header_len | u64 payload_len
    | u32 header_crc32 | u32 payload_crc32 | header JSON | array payload

``save_snapshot`` commits atomically: write to ``<path>.tmp``, fsync,
``os.replace`` onto ``path``, fsync the directory — a crash mid-write can
only ever leave the tmp file behind, never a half-written snapshot under
the committed name.  ``load_snapshot`` validates magic, declared lengths,
and both checksums before decoding; any mismatch (torn write, truncation,
bit corruption) raises ``SnapshotCorruption`` so recovery can fall back to
an earlier snapshot instead of silently loading garbage.
"""
from __future__ import annotations

import json
import os
import struct
import zlib
from dataclasses import asdict

import jax
import jax.numpy as jnp
import ml_dtypes  # noqa: F401  registers bfloat16 with numpy's dtype lookup
import numpy as np

from repro.core import coding
from repro.core.sharding import StagePlan
from repro.stores.store import StoreStats

MAGIC = b"REPROSN1"
VERSION = 1
_FIXED = struct.Struct("<IQQII")       # version, hlen, plen, hcrc, pcrc


class SnapshotCorruption(RuntimeError):
    """A snapshot failed structural or checksum validation (torn write,
    truncation, or bit corruption).  Recovery falls back to the previous
    good snapshot (``CheckpointManager.load_latest``)."""


def _treedef_type():
    return type(jax.tree.structure(0))


# ---------------------------------------------------------------------------
# Encode
# ---------------------------------------------------------------------------

def _enc(obj, arrays: list, blobs: list):
    """Recursively encode ``obj`` to a JSON-able node; array data lands in
    ``blobs`` with its geometry in ``arrays``."""
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    if isinstance(obj, (jax.Array, np.ndarray, np.generic)):
        is_jax = isinstance(obj, jax.Array)
        a = np.ascontiguousarray(np.asarray(jax.device_get(obj)))
        idx = len(arrays)
        arrays.append({"dtype": a.dtype.name, "shape": list(a.shape),
                       "nbytes": int(a.nbytes), "jax": is_jax})
        blobs.append(a.tobytes())
        return {"_t": "arr", "i": idx}
    if isinstance(obj, np.dtype):
        return {"_t": "dtype", "name": obj.name}
    if isinstance(obj, StoreStats):
        return {"_t": "StoreStats", "v": _enc(asdict(obj), arrays, blobs)}
    if isinstance(obj, coding.CodingScheme):
        return {"_t": "CodingScheme",
                "S": obj.num_shards, "C": obj.num_clients,
                "alpha": _enc(np.asarray(obj.alpha), arrays, blobs),
                "omega": _enc(np.asarray(obj.omega), arrays, blobs)}
    if isinstance(obj, coding.StackedRowSpec):
        return {"_t": "StackedRowSpec",
                "clients": [int(c) for c in obj.client_ids],
                "row_len": int(obj.row_len),
                "row_spec": _enc(obj.row_spec, arrays, blobs)}
    if isinstance(obj, StagePlan):
        return {"_t": "StagePlan", "stage": int(obj.stage),
                "shard_clients": _enc(obj.shard_clients, arrays, blobs)}
    if isinstance(obj, _treedef_type()):
        # the example-tree trick: a treedef is exactly the structure of the
        # tree it unflattens int placeholders into
        example = jax.tree.unflatten(obj, list(range(obj.num_leaves)))
        return {"_t": "treedef", "example": _enc(example, arrays, blobs)}
    if isinstance(obj, tuple):
        return {"_t": "tuple", "v": [_enc(x, arrays, blobs) for x in obj]}
    if isinstance(obj, list):
        return {"_t": "list", "v": [_enc(x, arrays, blobs) for x in obj]}
    if isinstance(obj, (set, frozenset)):
        return {"_t": "set", "v": [_enc(x, arrays, blobs)
                                   for x in sorted(obj, key=repr)]}
    if isinstance(obj, dict):
        return {"_t": "dict", "v": [[_enc(k, arrays, blobs),
                                     _enc(v, arrays, blobs)]
                                    for k, v in obj.items()]}
    raise TypeError(f"snapshot cannot encode {type(obj).__name__}: {obj!r}")


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def _dec(node, arrays: list):
    if node is None or isinstance(node, (bool, int, float, str)):
        return node
    t = node["_t"]
    if t == "arr":
        return arrays[node["i"]]
    if t == "dtype":
        return np.dtype(node["name"])
    if t == "StoreStats":
        return StoreStats(**_dec(node["v"], arrays))
    if t == "CodingScheme":
        return coding.CodingScheme(
            num_shards=node["S"], num_clients=node["C"],
            alpha=np.asarray(_dec(node["alpha"], arrays)),
            omega=np.asarray(_dec(node["omega"], arrays)))
    if t == "StackedRowSpec":
        return coding.StackedRowSpec(tuple(node["clients"]), node["row_len"],
                                     _dec(node["row_spec"], arrays))
    if t == "StagePlan":
        return StagePlan(stage=node["stage"],
                         shard_clients=_dec(node["shard_clients"], arrays))
    if t == "treedef":
        return jax.tree.structure(_dec(node["example"], arrays))
    if t == "tuple":
        return tuple(_dec(x, arrays) for x in node["v"])
    if t == "list":
        return [_dec(x, arrays) for x in node["v"]]
    if t == "set":
        return set(_dec(x, arrays) for x in node["v"])
    if t == "dict":
        return {_dec(k, arrays): _dec(v, arrays) for k, v in node["v"]}
    raise SnapshotCorruption(f"unknown node tag {t!r}")


def _decode_array(meta: dict, payload: bytes) -> object:
    a = np.frombuffer(payload[meta["off"]: meta["off"] + meta["nbytes"]],
                      dtype=np.dtype(meta["dtype"]))
    a = a.reshape(tuple(meta["shape"]))
    return jnp.asarray(a) if meta["jax"] else a.copy()


# ---------------------------------------------------------------------------
# File I/O
# ---------------------------------------------------------------------------

def save_snapshot(path: str, obj) -> int:
    """Serialize ``obj`` to ``path`` with an atomic rename-commit.  Returns
    the committed file size in bytes."""
    arrays: list = []
    blobs: list = []
    root = _enc(obj, arrays, blobs)
    off = 0
    for meta, blob in zip(arrays, blobs):
        meta["off"] = off
        off += len(blob)
    header = json.dumps({"version": VERSION, "arrays": arrays, "root": root},
                        separators=(",", ":")).encode()
    payload = b"".join(blobs)
    buf = b"".join([MAGIC,
                    _FIXED.pack(VERSION, len(header), len(payload),
                                zlib.crc32(header), zlib.crc32(payload)),
                    header, payload])
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(buf)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    dfd = os.open(os.path.dirname(os.path.abspath(path)), os.O_RDONLY)
    try:
        os.fsync(dfd)
    finally:
        os.close(dfd)
    return len(buf)


def load_snapshot(path: str):
    """Read, validate (magic, lengths, both checksums), and decode ``path``.
    Raises ``SnapshotCorruption`` on any validation failure."""
    try:
        with open(path, "rb") as f:
            buf = f.read()
    except OSError as exc:
        raise SnapshotCorruption(f"unreadable snapshot {path}: {exc}") from exc
    fixed_end = len(MAGIC) + _FIXED.size
    if len(buf) < fixed_end or buf[:len(MAGIC)] != MAGIC:
        raise SnapshotCorruption(f"{path}: bad magic or truncated preamble")
    version, hlen, plen, hcrc, pcrc = _FIXED.unpack_from(buf, len(MAGIC))
    if version != VERSION:
        raise SnapshotCorruption(f"{path}: unsupported version {version}")
    if len(buf) != fixed_end + hlen + plen:
        raise SnapshotCorruption(
            f"{path}: size {len(buf)} != declared {fixed_end + hlen + plen} "
            f"(torn write)")
    header = buf[fixed_end: fixed_end + hlen]
    payload = buf[fixed_end + hlen:]
    if zlib.crc32(header) != hcrc:
        raise SnapshotCorruption(f"{path}: header checksum mismatch")
    if zlib.crc32(payload) != pcrc:
        raise SnapshotCorruption(f"{path}: payload checksum mismatch")
    hd = json.loads(header)
    arrays = [_decode_array(meta, payload) for meta in hd["arrays"]]
    return _dec(hd["root"], arrays)
