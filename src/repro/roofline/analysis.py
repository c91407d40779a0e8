"""Roofline analysis from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), per device (the SPMD-partitioned
module's shapes ARE per-device):

    compute    = HLO_flops_dev / PEAK_FLOPS            (197 TF/s bf16, v5e)
    memory     = HLO_bytes_dev / HBM_BW                (819 GB/s)
    collective = ici_bytes/ICI_BW + dci_bytes/DCI_BW   (50 / 25 GB/s)

Collective bytes come from parsing the optimized HLO: every all-reduce /
all-gather / reduce-scatter / all-to-all / collective-permute, with ring-
model wire-byte factors and participant counts recovered from
``replica_groups`` (both explicit ``{{0,1},...}`` and iota
``[G,K]<=[dims]T(perm)`` forms are evaluated exactly).  Ops whose groups
span devices in different pods (id // 256 differs on the 512-chip mesh) are
charged to the slower DCI tier.
"""

from __future__ import annotations

import math
import re
from typing import Any

import numpy as np

PEAK_FLOPS = 197e12  # bf16 per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link (intra-pod)
DCI_BW = 25e9  # bytes/s (inter-pod)
POD_SIZE = 256

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0, "opaque": 0,
}

COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_RESULT_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_IOTA_RG_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?"
)
_EXPLICIT_RG_RE = re.compile(r"replica_groups=\{\{([^=]*?)\}\}")


def _shape_bytes(type_str: str) -> int:
    """Total bytes of a (possibly tuple) HLO type string."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _parse_replica_groups(line: str):
    """Returns (group_size k, crosses_pod bool) or (None, False)."""
    m = _IOTA_RG_RE.search(line)
    if m:
        g, k = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        n = math.prod(dims)
        ids = np.arange(n).reshape(dims)
        if m.group(4):
            perm = [int(x) for x in m.group(4).split(",")]
            ids = np.transpose(ids, perm)
        groups = ids.reshape(g, k)
        crosses = bool(((groups // POD_SIZE).max(axis=1)
                        != (groups // POD_SIZE).min(axis=1)).any())
        return k, crosses
    m = _EXPLICIT_RG_RE.search(line)
    if m:
        first = m.group(1).split("},{")[0]
        ids = [int(x) for x in first.split(",") if x.strip()]
        pods = {i // POD_SIZE for i in ids}
        return max(len(ids), 1), len(pods) > 1
    return None, False


def parse_collectives(hlo_text: str) -> dict[str, Any]:
    """Scan the optimized HLO for collective ops; returns byte totals."""
    # pass 1: symbol table result-name -> bytes (for operand lookups)
    sizes: dict[str, int] = {}
    lines = hlo_text.splitlines()
    for ln in lines:
        m = _RESULT_RE.match(ln)
        if m and "=" in ln:
            rhs = m.group(2)
            tm = _SHAPE_RE.search(rhs)
            if tm:
                # bytes of full (possibly tuple) result type: up to the op name
                paren = rhs.find(" ")
                type_part = rhs[: rhs.find(")")] if "(" in rhs else rhs
                sizes[m.group(1)] = _shape_bytes(rhs.split("(")[0])

    by_type: dict[str, float] = {c: 0.0 for c in COLLECTIVES}
    ici, dci = 0.0, 0.0
    n_ops = 0
    for ln in lines:
        stripped = ln.strip()
        m = _RESULT_RE.match(ln)
        if not m:
            continue
        rhs = m.group(2)
        opm = re.search(r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute)(?:-start|-done)?\(", rhs)
        if not opm:
            continue
        op = opm.group(1)
        if "-done(" in rhs:
            continue  # counted at -start
        out_bytes = _shape_bytes(rhs.split("(")[0])
        k, crosses = _parse_replica_groups(ln)
        k = k or 1
        ring = (k - 1) / k if k > 1 else 0.0
        if op == "all-reduce":
            wire = 2.0 * out_bytes * ring
        elif op == "all-gather":
            wire = out_bytes * ring
        elif op == "reduce-scatter":
            wire = out_bytes * (k - 1)  # input = out*k; moves in*(k-1)/k
        elif op == "all-to-all":
            wire = out_bytes * ring
        else:  # collective-permute
            wire = out_bytes
        by_type[op] += wire
        n_ops += 1
        if crosses:
            dci += wire
        else:
            ici += wire
    return {
        "by_type": by_type,
        "ici_bytes": ici,
        "dci_bytes": dci,
        "total_bytes": ici + dci,
        "n_ops": n_ops,
    }


def model_flops(cfg, cell, n_params_active: int) -> float:
    """Useful model FLOPs for the whole cell step (all chips)."""
    if cell.kind == "train":
        tokens = cell.global_batch * cell.seq_len
        return 6.0 * n_params_active * tokens
    if cell.kind == "prefill":
        tokens = cell.global_batch * cell.seq_len
        return 2.0 * n_params_active * tokens
    # decode: one token per sequence
    return 2.0 * n_params_active * cell.global_batch


def analyze_compiled(compiled, cfg, cell, mesh, policy,
                     lower_s: float = 0.0, compile_s: float = 0.0) -> dict:
    import jax

    from repro.roofline.hlo_cost import walk_hlo

    chips = math.prod(mesh.devices.shape)
    cost = compiled.cost_analysis() or {}

    hlo = compiled.as_text()
    # trip-count-aware walker (cost_analysis counts while bodies once)
    walked = walk_hlo(hlo, pod_size=POD_SIZE)
    flops_dev = float(walked.flops)
    bytes_dev = float(walked.bytes)
    coll = {
        "by_type": walked.coll_by_type,
        "ici_bytes": walked.coll_ici,
        "dci_bytes": walked.coll_dci,
        "total_bytes": walked.coll_ici + walked.coll_dci,
        "n_ops": walked.n_collectives,
        "while_trip_counts": walked.while_trip_counts,
    }

    t_compute = flops_dev / PEAK_FLOPS
    t_memory = bytes_dev / HBM_BW
    t_coll = coll["ici_bytes"] / ICI_BW + coll["dci_bytes"] / DCI_BW
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    dominant = max(terms, key=terms.get)

    from repro.models import active_params

    n_active = active_params(cfg)
    mf_total = model_flops(cfg, cell, n_active)
    mf_dev = mf_total / chips
    useful = mf_dev / flops_dev if flops_dev else 0.0

    mem = compiled.memory_analysis()
    mem_info = {}
    for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        if hasattr(mem, attr):
            mem_info[attr] = int(getattr(mem, attr))
    if not mem_info:
        mem_info["repr"] = str(mem)

    return {
        "arch": cfg.name,
        "shape": cell.name,
        "kind": cell.kind,
        "mesh": list(mesh.devices.shape),
        "chips": chips,
        "policy": {
            "fsdp": policy.fsdp,
            "seq_shard": policy.seq_shard,
            "attn_mode": policy.attn_mode,
            "attn_pad_heads": policy.attn_pad_heads,
            "shard_kv_heads": policy.shard_kv_heads,
            "kv_seq_shard": policy.kv_seq_shard,
            "num_microbatches": policy.num_microbatches,
            "dp_axes": list(policy.dp_axes),
        },
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "raw_cost_analysis": {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        },
        "collectives": coll,
        "terms": terms,
        "dominant": dominant,
        "model_flops_per_device": mf_dev,
        "useful_flop_ratio": useful,
        "roofline_fraction": min(useful, 1.0) if dominant == "compute_s" else
            (t_compute / max(max(terms.values()), 1e-30)) * min(useful, 1.0),
        "memory_analysis": mem_info,
        "timings": {"lower_s": lower_s, "compile_s": compile_s},
    }
