"""Deterministic binary framing for the FL coordinator wire (ISSUE 8).

One frame format carries everything that crosses the service boundary:

``magic "RWF1" | u32 meta_len | meta JSON (sorted keys, compact) |
u32 n_buffers | buffer*``

and each buffer (emitted in sorted-name order) is

``u16 name_len | name utf-8 | u8 dtype_len | numpy dtype.str |
u8 ndim | ndim x u32 dims | u64 data_len | raw C-order bytes``

All integers are little-endian.  The payload bytes are the arrays'
exact memory images, so a round-trip is bit-identical and the framed
payload size of a :class:`~repro.fed.codecs.WireMsg` equals
``msg.bits / 8`` — the measured on-wire cost IS the codec's claimed
cost, with the framing overhead (`len(frame) - payload`) accounted
separately.

Two client/server payload shapes ride the frame:

* ``dumps_msg`` / ``loads_msg`` — one ``WireMsg`` (the codec tag
  travels in the meta dict, buffers by name);
* ``dumps_tree`` / ``loads_tree`` — an arbitrary pytree (the global
  model + algorithm state on downlink), leaves named by their
  ``jax.tree_util.keystr`` path and rebuilt against a template so the
  receiver recovers the exact structure and dtypes.

Stdlib + numpy only — no pickle (unsafe across trust boundaries), no
third-party serializers.
"""
from __future__ import annotations

import json
import struct
from typing import Any, Dict, Tuple

import jax
import numpy as np

from ..codecs import WireMsg

MAGIC = b"RWF1"

_U16_MAX = 0xFFFF
_U32_MAX = 0xFFFFFFFF


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise ValueError(f"serde: malformed frame — {what}")


def _to_ndarray(name: str, value: Any) -> np.ndarray:
    if hasattr(value, "dtype") and jax.dtypes.issubdtype(
            value.dtype, jax.dtypes.prng_key):
        raise TypeError(
            f"serde: buffer {name!r} is a PRNG key array — frame its "
            "jax.random.key_data(...) uint32 image instead")
    arr = np.asarray(value, order="C")     # keeps 0-d shapes
    if arr.dtype == object:
        raise TypeError(f"serde: buffer {name!r} is not a numeric array")
    return arr


def payload_bits(buffers: Dict[str, Any]) -> int:
    """Summed raw-array bits — the frame's payload (sans framing)."""
    return sum(int(_to_ndarray(k, v).nbytes) * 8 for k, v in buffers.items())


# ---------------------------------------------------------------------------
# the frame
# ---------------------------------------------------------------------------

def pack_frame(meta: Dict[str, Any], buffers: Dict[str, Any]) -> bytes:
    """Frame ``meta`` (JSON-able dict) + named arrays into bytes.

    Buffers are written in sorted-name order, so equal inputs produce
    byte-identical frames regardless of dict insertion order.
    """
    mb = json.dumps(meta, sort_keys=True,
                    separators=(",", ":")).encode("utf-8")
    parts = [MAGIC, struct.pack("<I", len(mb)), mb,
             struct.pack("<I", len(buffers))]
    for name in sorted(buffers):
        arr = _to_ndarray(name, buffers[name])
        nb = name.encode("utf-8")
        ds = arr.dtype.str.encode("ascii")
        if len(nb) > _U16_MAX or len(ds) > 255 or arr.ndim > 255:
            raise ValueError(f"serde: buffer {name!r} exceeds frame limits")
        if any(d > _U32_MAX for d in arr.shape):
            raise ValueError(f"serde: buffer {name!r} dim exceeds u32")
        parts.append(struct.pack("<H", len(nb)))
        parts.append(nb)
        parts.append(struct.pack("<B", len(ds)))
        parts.append(ds)
        parts.append(struct.pack("<B", arr.ndim))
        if arr.ndim:
            parts.append(struct.pack(f"<{arr.ndim}I", *arr.shape))
        parts.append(struct.pack("<Q", arr.nbytes))
        parts.append(arr.tobytes(order="C"))
    return b"".join(parts)


def unpack_frame(data: bytes) -> Tuple[Dict[str, Any],
                                       Dict[str, np.ndarray]]:
    """Inverse of :func:`pack_frame`; bit-exact array recovery."""
    _require(data[:4] == MAGIC, f"bad magic {data[:4]!r}")
    off = 4

    def take(n: int) -> bytes:
        nonlocal off
        _require(off + n <= len(data), "truncated frame")
        out = data[off:off + n]
        off += n
        return out

    (meta_len,) = struct.unpack("<I", take(4))
    meta = json.loads(take(meta_len).decode("utf-8"))
    (n_bufs,) = struct.unpack("<I", take(4))
    buffers: Dict[str, np.ndarray] = {}
    for _ in range(n_bufs):
        (name_len,) = struct.unpack("<H", take(2))
        name = take(name_len).decode("utf-8")
        (dtype_len,) = struct.unpack("<B", take(1))
        dtype = np.dtype(take(dtype_len).decode("ascii"))
        (ndim,) = struct.unpack("<B", take(1))
        shape = struct.unpack(f"<{ndim}I", take(4 * ndim)) if ndim else ()
        (nbytes,) = struct.unpack("<Q", take(8))
        expect = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
        _require(nbytes == expect,
                 f"buffer {name!r} size/shape mismatch")
        arr = np.frombuffer(take(nbytes), dtype=dtype).reshape(shape)
        _require(name not in buffers, f"duplicate buffer {name!r}")
        buffers[name] = arr
    _require(off == len(data), f"{len(data) - off} trailing bytes")
    return meta, buffers


def framing_bits(frame: bytes, buffers: Dict[str, Any]) -> int:
    """Frame bytes NOT attributable to array payload, in bits."""
    return len(frame) * 8 - payload_bits(buffers)


# ---------------------------------------------------------------------------
# WireMsg <-> bytes
# ---------------------------------------------------------------------------

def dumps_msg(msg: WireMsg, **meta: Any) -> bytes:
    """Serialize one ``WireMsg``; extra keyword meta rides the frame
    (round index, client id, aggregation weight, last local loss)."""
    if "codec" in meta:
        raise ValueError("serde: 'codec' meta key is reserved")
    return pack_frame(dict(meta, codec=msg.codec), msg.buffers)


def loads_msg(data: bytes) -> Tuple[WireMsg, Dict[str, Any]]:
    meta, buffers = unpack_frame(data)
    _require("codec" in meta, "WireMsg frame missing 'codec' meta")
    meta = dict(meta)
    return WireMsg(meta.pop("codec"), dict(buffers)), meta


# ---------------------------------------------------------------------------
# pytree <-> bytes (downlink model + state)
# ---------------------------------------------------------------------------

def _tree_buffers(tree: Any) -> Dict[str, Any]:
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(path): leaf for path, leaf in leaves}


def tree_payload_bits(tree: Any) -> int:
    """Raw bits the tree's leaves occupy inside a frame."""
    return payload_bits(_tree_buffers(tree))


def dumps_tree(tree: Any, **meta: Any) -> bytes:
    """Serialize any pytree of arrays; leaf names are keystr paths."""
    return pack_frame(dict(meta), _tree_buffers(tree))


def loads_tree(data: bytes, template: Any) -> Tuple[Any, Dict[str, Any]]:
    """Rebuild a pytree with ``template``'s structure from a frame.

    The sender and receiver derive leaf names from the SAME structure,
    so the name set must match exactly — a mismatch means the two sides
    disagree about the model and is an error, not a best-effort merge.
    """
    meta, buffers = unpack_frame(data)
    paths, treedef = jax.tree_util.tree_flatten_with_path(template)
    names = [jax.tree_util.keystr(path) for path, _ in paths]
    missing = [n for n in names if n not in buffers]
    extra = sorted(set(buffers) - set(names))
    _require(not missing and not extra,
             f"tree/template mismatch (missing={missing}, extra={extra})")
    leaves = []
    for name, (_, tmpl) in zip(names, paths):
        arr = buffers[name]
        _require(arr.dtype == np.dtype(tmpl.dtype)
                 and arr.shape == tuple(tmpl.shape),
                 f"leaf {name!r}: got {arr.dtype}{arr.shape}, template "
                 f"{np.dtype(tmpl.dtype)}{tuple(tmpl.shape)}")
        leaves.append(arr)
    return jax.tree_util.tree_unflatten(treedef, leaves), meta
