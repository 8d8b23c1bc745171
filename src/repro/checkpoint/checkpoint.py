"""Checkpointing: msgpack + zstd of flattened pytrees.

Arrays are gathered to host (fully-addressable single-process here; on a real
multi-host pod each host would write its addressable shards — the format
already keys leaves by tree path, so per-shard files compose). Restore takes
a ``target`` template pytree (params/opt-state structure with NamedTuples)
and refills its leaves, preserving shardings via device_put-like placement by
the caller.
"""
from __future__ import annotations

import os
import re
from typing import Any, Optional

import jax
import msgpack
import numpy as np
import zstandard

_ZSTD_MAGIC = b"\x28\xb5\x2f\xfd"


def _compress(raw: bytes) -> bytes:
    return zstandard.ZstdCompressor(level=3).compress(raw)


def _decompress(blob: bytes) -> bytes:
    return zstandard.ZstdDecompressor().decompress(blob)


def _key_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def save(path: str, tree: Any, step: int = 0,
         keep: Optional[int] = None, meta: Optional[dict] = None) -> str:
    """Write ``<path>/ckpt_<step>.msgpack.zst``. Returns the file path.

    The write is atomic (tmp file + ``os.replace``): a run killed mid-write
    never leaves a truncated checkpoint behind for ``latest_step`` to find.
    ``keep=N`` prunes all but the N highest-step files AFTER the new file is
    durable (oldest steps first — a long-run cadence must not fill the
    disk); ``keep=None``/0 retains everything. ``meta`` is a small
    msgpack-able dict stored alongside the leaves — the trainers record
    their mesh geometry here so ``validate_restore`` can reject (or
    ``repro.elastic`` can reshard) a mismatched restore up front.
    """
    os.makedirs(path, exist_ok=True)
    leaves_with_paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    payload = {"step": step, "meta": dict(meta or {}), "leaves": {}}
    for kp, leaf in leaves_with_paths:
        arr = np.asarray(jax.device_get(leaf))
        payload["leaves"][_key_str(kp)] = {
            "dtype": str(arr.dtype), "shape": list(arr.shape),
            "data": arr.tobytes()}
    raw = msgpack.packb(payload, use_bin_type=True)
    fname = os.path.join(path, f"ckpt_{step}.msgpack.zst")
    tmp = fname + f".tmp.{os.getpid()}"
    try:
        with open(tmp, "wb") as f:
            f.write(_compress(raw))
        os.replace(tmp, fname)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    if keep:
        prune(path, keep)
    return fname


def all_steps(path: str) -> list:
    """Sorted step numbers of every checkpoint under ``path``."""
    if not os.path.isdir(path):
        return []
    return sorted(int(m.group(1)) for fn in os.listdir(path)
                  if (m := re.match(r"ckpt_(\d+)\.msgpack\.zst$", fn)))


def prune(path: str, keep: int) -> list:
    """Delete all but the ``keep`` highest-step checkpoint files. Returns
    the pruned step numbers (ascending — oldest removed first)."""
    if keep < 1:
        raise ValueError(f"keep must be >= 1, got {keep}")
    doomed = all_steps(path)[:-keep] if keep else []
    for s in doomed:
        os.remove(os.path.join(path, f"ckpt_{s}.msgpack.zst"))
    return doomed


def latest_step(path: str) -> Optional[int]:
    steps = all_steps(path)
    return steps[-1] if steps else None


def read_meta(path: str, step: Optional[int] = None) -> Optional[dict]:
    """The geometry/meta dict stored with a checkpoint (``save(meta=...)``)
    — None for files written before meta existed (those can only assert
    same-mesh restores; there is nothing to validate against)."""
    if step is None:
        step = latest_step(path)
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {path}")
    fname = os.path.join(path, f"ckpt_{step}.msgpack.zst")
    with open(fname, "rb") as f:
        raw = _decompress(f.read())
    payload = msgpack.unpackb(raw, raw=False)
    return payload.get("meta") or None


def validate_restore(path: str, expect, step: Optional[int] = None, *,
                     reshard: bool = False):
    """Up-front geometry check BEFORE any leaf is decoded or placed.

    ``expect`` is the restoring experiment's ``repro.elastic.MeshGeometry``.
    Raises ``repro.elastic.ReshardError`` naming both geometries when the
    class count differs (never reshardable) or when the mesh shape differs
    and ``reshard`` was not requested — instead of the shape error the
    mismatch used to hit deep inside jax. Returns the checkpoint's stored
    geometry (== ``expect`` for pre-meta checkpoints).
    """
    from repro.elastic.plan import geometry_from_meta, validate_geometry
    meta = read_meta(path, step)
    src = geometry_from_meta(meta, expect)
    validate_geometry(src, expect, reshard=reshard)
    return src


def restore(path: str, target: Any, step: Optional[int] = None):
    """Refill ``target``'s leaves from a checkpoint. Returns (tree, step)."""
    if step is None:
        step = latest_step(path)
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {path}")
    fname = os.path.join(path, f"ckpt_{step}.msgpack.zst")
    with open(fname, "rb") as f:
        raw = _decompress(f.read())
    payload = msgpack.unpackb(raw, raw=False)
    stored = payload["leaves"]
    leaves_with_paths, treedef = jax.tree_util.tree_flatten_with_path(target)
    new_leaves = []
    for kp, leaf in leaves_with_paths:
        key = _key_str(kp)
        if key not in stored:
            raise KeyError(f"checkpoint missing leaf {key!r}")
        rec = stored[key]
        arr = np.frombuffer(rec["data"], dtype=rec["dtype"]).reshape(rec["shape"])
        new_leaves.append(jax.numpy.asarray(arr))
    return jax.tree_util.tree_unflatten(treedef, new_leaves), payload["step"]
