"""Three-term roofline from the dry-run results (deliverable g).

    compute term    = HLO_FLOPs / (chips x peak_FLOP/s)
    memory term     = HLO_bytes / (chips x HBM_bw)
    collective term = collective_bytes / (chips x link_bw)

HLO numbers are the loop-corrected per-device totals from roofline.hlo (the
dry-run records per-device SPMD programs, so 'chips x' is already folded in:
terms below use per-device values against per-chip peaks).

MODEL_FLOPS (the 'useful work') is analytic: 6*N*D for dense training
(N = params, D = tokens), 6*N_active*D for MoE, 2*N(+attn) for decode.
The ratio MODEL_FLOPS / HLO_FLOPs measures how much compiled compute is
useful — it surfaces remat recompute, replicated attention heads, dropped/
padded expert capacity, and the head's logits work.

Per-chip peaks come from ``PEAKS``, keyed by the ``device_kind`` that jax
reports. The dry run lowers for a v5e pod, so its records are read against
the v5e row unless they name another kind.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from typing import Optional

from repro.configs.base import (INPUT_SHAPES, ModelConfig, get_model_config,
                                normalize_arch_id)

# Published per-chip peaks by ``jax.devices()[i].device_kind``. Source:
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at
# 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 200e9,
                    "hbm_bytes": 16e9},
}
DRYRUN_DEVICE_KIND = "TPU v5 lite"   # the chip launch/dryrun.py lowers for


def peaks(device_kind: str) -> dict:
    """Per-chip peaks of ``device_kind``; a kind without published figures
    in ``PEAKS`` is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


# ---------------------------------------------------------------------------
# analytic MODEL_FLOPS
# ---------------------------------------------------------------------------


def active_params(cfg: ModelConfig) -> float:
    """Total params, counting only top-k (+shared) experts for MoE."""
    import jax

    from repro.models import lm
    sds = jax.eval_shape(lambda: lm.init_model(jax.random.PRNGKey(0), cfg))
    total = sum(l.size for l in jax.tree.leaves(sds))
    if cfg.moe is None:
        return float(total)
    m = cfg.moe
    expert_p = cfg.n_layers * 3 * cfg.d_model * m.d_ff * m.n_experts
    active_expert_p = expert_p * (m.top_k / m.n_experts)
    return float(total - expert_p + active_expert_p)


def model_flops(cfg: ModelConfig, shape_name: str) -> float:
    """Analytic useful FLOPs of one GLOBAL step (all chips together)."""
    shape = INPUT_SHAPES[shape_name]
    n_act = active_params(cfg)
    if shape.mode == "train":
        tokens = shape.global_batch * (1 if cfg.family == "cnn"
                                       else shape.seq_len)
        flops = 6.0 * n_act * tokens
        # causal attention score/context matmuls (not in 6ND)
        if cfg.n_heads and cfg.family != "cnn":
            hd = cfg.resolved_head_dim
            win = cfg.sliding_window or shape.seq_len
            eff = min(win, shape.seq_len)
            flops += (6.0 * 2.0 * shape.global_batch * cfg.n_layers
                      * cfg.n_heads * hd * shape.seq_len * eff / 2)
        return flops
    if shape.mode == "prefill":
        tokens = shape.global_batch * shape.seq_len
        flops = 2.0 * n_act * tokens
        if cfg.n_heads and cfg.family != "cnn":
            hd = cfg.resolved_head_dim
            win = cfg.sliding_window or shape.seq_len
            eff = min(win, shape.seq_len)
            flops += (2.0 * 2.0 * shape.global_batch * cfg.n_layers
                      * cfg.n_heads * hd * shape.seq_len * eff / 2)
        return flops
    # decode: one token per sequence
    flops = 2.0 * n_act * shape.global_batch
    if cfg.n_heads and cfg.family != "ssm":
        hd = cfg.resolved_head_dim
        win = cfg.sliding_window or shape.seq_len
        kv_len = min(win, shape.seq_len)
        flops += (2.0 * 2.0 * shape.global_batch * cfg.n_layers
                  * cfg.n_heads * hd * kv_len)
    return flops


# ---------------------------------------------------------------------------
# the report
# ---------------------------------------------------------------------------


@dataclass
class RooflineRow:
    arch: str
    shape: str
    mesh: str
    n_chips: int
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float
    hlo_flops_per_dev: float
    useful_ratio: float
    peak_gib: float
    fits: bool

    def terms(self):
        return {"compute": self.compute_s, "memory": self.memory_s,
                "collective": self.collective_s}


def analyze_record(rec: dict) -> Optional[RooflineRow]:
    if "error" in rec:
        return None
    n_chips = 512 if rec["mesh"] == "2x16x16" else 256
    chip = peaks(rec.get("device_kind", DRYRUN_DEVICE_KIND))
    flops_dev = rec["hlo"]["flops"]
    bytes_dev = rec["hlo"]["bytes"]
    coll_dev = rec["collectives"]["total_bytes"]
    compute_s = flops_dev / chip["flops"]
    memory_s = bytes_dev / chip["hbm_bw"]
    coll_s = coll_dev / chip["ici_bw"]
    dominant = max(
        (("compute", compute_s), ("memory", memory_s),
         ("collective", coll_s)), key=lambda kv: kv[1])[0]
    cfg = get_model_config(normalize_arch_id(rec["arch"]))
    mf = model_flops(cfg, rec["shape"])
    useful = mf / max(flops_dev * n_chips, 1.0)
    mem = rec["memory"]
    per_dev = mem["argument_bytes"] + max(mem["temp_bytes"],
                                          mem.get("peak_bytes", 0))
    return RooflineRow(
        arch=rec["arch"], shape=rec["shape"], mesh=rec["mesh"],
        n_chips=n_chips, compute_s=compute_s, memory_s=memory_s,
        collective_s=coll_s, dominant=dominant, model_flops=mf,
        hlo_flops_per_dev=flops_dev, useful_ratio=useful,
        peak_gib=per_dev / 2**30, fits=per_dev <= chip["hbm_bytes"])


def load_rows(path: str, mesh: Optional[str] = None):
    rows = []
    seen = set()
    for line in open(path):
        rec = json.loads(line)
        key = (rec.get("arch"), rec.get("shape"), rec.get("mesh"),
               rec.get("knn", False))
        if key in seen:
            continue
        seen.add(key)
        if mesh and rec.get("mesh") != mesh:
            continue
        row = analyze_record(rec)
        if row:
            rows.append(row)
    return rows


def bottleneck_sentence(row: RooflineRow) -> str:
    """One sentence on what would move the dominant term down."""
    if row.dominant == "collective":
        return ("collective-bound: cut cross-device bytes (KNN-softmax "
                "active classes shrink the feature all-gather + head work; "
                "DGC shrinks data-parallel grad traffic; larger microbatches "
                "amortize FSDP gathers)")
    if row.dominant == "memory":
        return ("HBM-bound: raise arithmetic intensity (fuse softmax-CE "
                "streaming kernel, larger attention kv blocks, bf16 "
                "activations end-to-end)")
    return ("compute-bound: good — push MFU via MXU-aligned tiles and drop "
            "redundant/replicated compute (replicated attention heads, "
            "padded expert capacity)")


def to_markdown(rows, hillclimbed=()) -> str:
    out = ["| arch | shape | mesh | compute(s) | memory(s) | collective(s) | "
           "dominant | MODEL_FLOPS | useful | peak GiB/dev | fits 16G |",
           "|---|---|---|---|---|---|---|---|---|---|---|"]
    for r in sorted(rows, key=lambda r: (r.arch, r.shape, r.mesh)):
        mark = " **(hillclimbed)**" if (r.arch, r.shape) in hillclimbed else ""
        out.append(
            f"| {r.arch}{mark} | {r.shape} | {r.mesh} | {r.compute_s:.2e} | "
            f"{r.memory_s:.2e} | {r.collective_s:.2e} | {r.dominant} | "
            f"{r.model_flops:.2e} | {r.useful_ratio:.2f} | "
            f"{r.peak_gib:.1f} | {'yes' if r.fits else 'NO'} |")
    return "\n".join(out)
