"""Operations and bytes the algorithms need, from their shapes.

Counts are of what the algorithm needs, not of what an implementation
happens to do: recomputation (the backward kernels' second scoring pass)
and the refresh are not counted. float32 operands are 4 bytes.
"""
from __future__ import annotations

import json
import os

F32 = 4


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind`` (``peaks.json``); an
    unknown kind is an error, never a default."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as fh:
        table = json.load(fh)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return table[device_kind]


def train_flops_per_sample(head: str, classes: int, d: int,
                           active: int = 0) -> float:
    """Forward and backward of the head per sample: 2 for the logits and
    4 for the two gradient products, per scored class and feature."""
    scored = classes if head == "full" else active
    return 6.0 * scored * d


def ce_kernels(b: int, v: int, d: int) -> tuple:
    """(flops, bytes) of the fused CE forward and backward kernels on one
    micro-batch of ``b`` rows against ``v`` class rows: the forward reads
    the features and the class rows; the backward reads both again and
    writes the class rows' gradient and the features' gradient."""
    flops = 2.0 * b * v * d + 4.0 * b * v * d
    fwd = (b * d + v * d) * F32
    bwd = (b * d + v * d + v * d + b * d) * F32
    return flops, float(fwd + bwd)


def sparse_ce_kernels(b: int, m: int, d: int) -> tuple:
    """(flops, bytes) of the sparse CE forward and backward kernels on one
    micro-batch of ``b`` rows against ``m`` active class rows, gathered
    from the shard: the forward reads the features and the active rows;
    the backward reads both again and writes the active rows' gradient
    and the features' gradient."""
    flops = 2.0 * b * m * d + 4.0 * b * m * d
    fwd = (b * d + m * d) * F32
    bwd = (b * d + m * d + m * d + b * d) * F32
    return flops, float(fwd + bwd)


def scan(b: int, v: int, d: int) -> tuple:
    """(flops, bytes) of an exact top-k scan of ``b`` queries over ``v``
    class rows: the scores, and one read of the class rows."""
    return 2.0 * b * v * d, float(v * d * F32 + b * d * F32)


def bound_s(flops: float, nbytes: float, peak: dict) -> tuple:
    """(least seconds, which bound) on one chip of ``peak``."""
    t_c = flops / peak["flops_bf16"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
