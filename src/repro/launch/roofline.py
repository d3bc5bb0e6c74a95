"""Roofline-term extraction from compiled dry-run artifacts.

Hardware model (TPU v5e): 197 TFLOP/s bf16 per chip, 819 GB/s HBM,
~50 GB/s/link ICI. Terms are per-step times in seconds:

  compute    = HLO_FLOPs / (chips * peak)     [cost_analysis is per-device,
                                               so: flops_per_device / peak]
  memory     = HLO_bytes / (chips * hbm_bw)   [ditto]
  collective = bytes moved per device over ICI / link_bw

Collective bytes come from parsing the (already SPMD-partitioned,
per-device) HLO: every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute contributes its ring-algorithm traffic:
  all-reduce     2 * out_bytes * (g-1)/g
  all-gather     out_bytes * (g-1)/g
  reduce-scatter in_bytes ~= out_bytes * (g-1)        (per-device send)
  all-to-all     out_bytes * (g-1)/g
  collective-permute out_bytes
where g is the replica-group size parsed from the op.
"""
from __future__ import annotations

import re

PEAK_FLOPS = 197e12  # bf16 / chip
HBM_BW = 819e9  # bytes / s / chip
LINK_BW = 50e9  # bytes / s / link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_ARRAY_RE = re.compile(r"(pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64|c64|c128)\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|[\w\[\],{}\/ ]+?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(",
    re.M,
)
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[\d+\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _ARRAY_RE.findall(type_str):
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes_from_hlo(hlo: str) -> dict:
    """Returns {op_kind: bytes_moved_per_device} + totals."""
    out: dict[str, float] = {}
    count = 0
    lines = hlo.splitlines()
    for line in lines:
        m = _COLL_RE.match(line)
        if not m:
            continue
        type_str, kind = m.group(1), m.group(2)
        nbytes = _shape_bytes(type_str)
        if nbytes == 0:
            continue
        g = None
        gm = _GROUPS_RE.search(line)
        if gm:
            g = len(gm.group(1).split(","))
        else:
            gm = _GROUPS_IOTA_RE.search(line)
            if gm:
                g = int(gm.group(2))
        g = g or 2
        if kind == "all-reduce":
            moved = 2 * nbytes * (g - 1) / g
        elif kind == "all-gather":
            moved = nbytes * (g - 1) / g
        elif kind == "reduce-scatter":
            moved = nbytes * (g - 1)
        elif kind == "all-to-all":
            moved = nbytes * (g - 1) / g
        else:  # collective-permute
            moved = nbytes
        out[kind] = out.get(kind, 0.0) + moved
        count += 1
    out["total_bytes_per_device"] = sum(
        v for k, v in out.items() if k != "total_bytes_per_device"
    )
    out["n_ops"] = count
    return out


def roofline_terms(rec: dict) -> dict:
    fpd = max(rec.get("flops_per_device", 0), 0)
    bpd = max(rec.get("bytes_per_device", 0), 0)
    cpd = rec.get("collectives", {}).get("total_bytes_per_device", 0)
    compute_s = fpd / PEAK_FLOPS
    memory_s = bpd / HBM_BW
    coll_s = cpd / LINK_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s, "collective_s": coll_s}
    dom = max(terms, key=terms.get)
    bound = max(compute_s, memory_s, coll_s)
    mf = rec.get("model_flops", 0)
    n_chips = rec.get("n_chips", 1)
    terms["dominant"] = dom
    terms["step_time_lower_bound_s"] = bound
    if mf and fpd > 0:
        terms["useful_flop_ratio"] = mf / (fpd * n_chips)
        # fraction of roofline: useful work at peak vs. bound-implied time
        terms["roofline_fraction"] = (mf / (n_chips * PEAK_FLOPS)) / bound if bound else 0.0
    return terms


def useful_flops(arch, shape) -> float:
    """MODEL_FLOPS: 6*N*D (train) / 2*N*D (inference), N = active params.

    GNNs: parameter-matmul work per node/edge, x3 for bwd. Rough by design —
    it is the sanity ratio against compiled FLOPs, not a score.
    """
    fam = arch.family
    if fam == "lm":
        cfg = arch.config
        n_act = cfg.active_param_count()
        if shape.kind == "train":
            return 6.0 * n_act * shape.global_batch * shape.seq_len
        if shape.kind == "prefill":
            return 2.0 * n_act * shape.global_batch * shape.seq_len
        # decode: one token per sequence + attention over the cache
        attn = (
            2.0 * cfg.n_layers * cfg.n_kv * cfg.d_head * 2 * shape.seq_len
            * shape.global_batch
        )
        return 2.0 * n_act * shape.global_batch + attn
    if fam == "recsys":
        cfg = arch.config
        d = cfg.embed_dim
        enc = cfg.n_blocks * (4 * d * d + 8 * d * d)  # attn + ffn per token
        attn = cfg.n_blocks * 2 * cfg.seq_len * d  # score+mix per token
        per_seq = cfg.seq_len * (enc + attn)
        if shape.kind == "train":
            head = cfg.n_mask * (1 + cfg.n_negatives) * d * 2
            return 3.0 * shape.batch * (per_seq + head)
        if shape.kind == "retrieval":
            return shape.batch * per_seq + 2.0 * shape.n_candidates * d
        return shape.batch * (per_seq + 2.0 * cfg.item_vocab * d)
    # gnn
    from repro.launch.steps import gnn_batch_dims, gnn_shape_config

    cfg = gnn_shape_config(arch, shape)
    N, E = gnn_batch_dims(shape)
    d = cfg.d_hidden
    if arch.id == "gin-tu":
        per_node = 2 * (cfg.d_in * d + cfg.n_layers * 2 * d * d)
        per_edge = cfg.n_layers * d
        fwd = N * per_node + E * per_edge
    elif arch.id == "egnn":
        per_edge = cfg.n_layers * 2 * ((2 * d + 1) * d + d * d + d * d + d)
        per_node = cfg.n_layers * 2 * (2 * d * d + d * d)
        fwd = N * per_node + E * per_edge
    elif arch.id == "meshgraphnet":
        per_edge = cfg.n_layers * 2 * (3 * d * d + d * d + d * d)
        per_node = cfg.n_layers * 2 * (2 * d * d + d * d + d * d)
        fwd = N * per_node + E * per_edge
    else:  # equiformer-v2
        n_m = cfg.m_max + 1
        so2 = (cfg.l_max + 1) * d * d + sum(
            (cfg.l_max + 1 - m) * (2 * d) * (2 * d) for m in range(1, n_m)
        )
        per_edge = cfg.n_layers * 2 * 2 * so2  # x2 two-pass softmax
        per_node = cfg.n_layers * 2 * (cfg.n_heads * d * d + (cfg.l_max + 1) * d * d)
        fwd = N * per_node + E * per_edge
    return 3.0 * fwd
