"""Roofline-term extraction from a compiled dry-run artifact.

Three terms (seconds), per device, from the peaks of the target chip:

  compute    = HLO_FLOPs / peak_flops
  memory     = HLO_bytes / hbm_bw
  collective = collective_bytes / link_bw

``cost_analysis`` of the partitioned module reports per-device FLOPs and
bytes. Collective bytes are parsed from the post-optimization HLO text:
the summed operand sizes of all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute ops (per-device shard shapes).
"""

from __future__ import annotations

import re
from typing import Dict

#: Published per-chip peaks, keyed by ``jax.Device.device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
#: 819 GB/s HBM, 1,600 Gbit/s of inter-chip interconnect (4 links of
#: ~50 GB/s each).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table row of ``device_kind``; an unknown kind is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_COLLECTIVE_OPS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)
# post-optimization HLO: "%name = f32[8,512,576]{2,1,0} all-gather(%op), ..."
# (operands carry no type annotations, so sizes come from the RESULT
# shape + replica_groups)
_OP_LINE_RE = re.compile(
    r"=\s*((?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\])\S*)\s+"
    r"(" + "|".join(_COLLECTIVE_OPS) + r")(-start|-done)?\("
)
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _result_bytes(result: str) -> int:
    return sum(_shape_bytes(m.group(1), m.group(2))
               for m in _SHAPE_RE.finditer(result))


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 2


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-opcode *wire* bytes per device (ring-model) of collectives.

    all-gather: (g-1)/g * result  received per device
    all-reduce: 2*(g-1)/g * operand (reduce-scatter + all-gather phases)
    reduce-scatter: (g-1)/g * operand  (operand = result * g)
    all-to-all: (g-1)/g * result
    collective-permute: result
    -done ops are skipped (their -start pair is counted).
    """
    out: Dict[str, int] = {op: 0 for op in _COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        m = _OP_LINE_RE.search(line)
        if not m:
            continue
        result, op, phase = m.group(1), m.group(2), m.group(3)
        if phase == "-done":
            continue
        rb = _result_bytes(result)
        g = _group_size(line)
        if g <= 1:
            continue
        if op == "all-gather":
            wire = rb * (g - 1) // g
        elif op == "all-reduce":
            wire = 2 * rb * (g - 1) // g
        elif op == "reduce-scatter":
            wire = rb * (g - 1)  # operand = result * g
        elif op == "all-to-all":
            wire = rb * (g - 1) // g
        else:  # collective-permute
            wire = rb
        out[op] += wire
    return out


def roofline_terms(flops: float, bytes_accessed: float,
                   coll_bytes: int, *, device_kind: str
                   ) -> Dict[str, float]:
    pk = peaks(device_kind)
    compute = flops / pk["flops"]
    memory = bytes_accessed / pk["hbm_bw"]
    collective = coll_bytes / pk["link_bw"]
    terms = {"compute_s": compute, "memory_s": memory,
             "collective_s": collective}
    dom = max(terms, key=terms.get)
    terms["bottleneck"] = dom.replace("_s", "")
    total = max(compute, memory, collective)
    terms["step_time_lower_bound_s"] = total
    return terms


def model_flops(cfg, shape) -> float:
    """6*N*D (dense) or 6*N_active*D (MoE) useful-model FLOPs for the cell.

    For decode cells D = global_batch tokens (one step); for train /
    prefill D = global_batch * seq_len. Training counts fwd+bwd (6N);
    inference counts 2N.
    """
    n_active = active_params(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch


def active_params(cfg) -> float:
    """Active (per-token) parameter count from the config, analytically."""
    d, V = cfg.d_model, cfg.vocab_size
    total = V * d  # embedding (tied head counted once for compute)
    if not cfg.tie_embeddings:
        total += V * d
    for kind in cfg.layer_kinds:
        total += _layer_params(cfg, kind, active_only=True)
    return float(total)


def _layer_params(cfg, kind: str, active_only: bool = False) -> float:
    d, hd = cfg.d_model, cfg.head_dim
    H, KH = cfg.n_heads, cfg.n_kv_heads
    p = 0.0
    if kind in ("attn", "local_attn", "enc_attn", "moe_attn", "dense_attn",
                "xattn"):
        p += d * H * hd + 2 * d * KH * hd + H * hd * d
        if kind == "xattn":
            p += d * H * hd + 2 * d * KH * hd + H * hd * d
    elif kind in ("mla_attn", "mla_moe_attn"):
        m = cfg.mla
        qk = m.qk_nope_head_dim + m.qk_rope_head_dim
        p += d * H * qk
        p += d * (m.kv_lora_rank + m.qk_rope_head_dim)
        p += m.kv_lora_rank * H * (m.qk_nope_head_dim + m.v_head_dim)
        p += H * m.v_head_dim * d
    elif kind == "rg_lru":
        lw = cfg.lru_width
        p += 2 * d * lw + lw * d  # branches + out
        p += 4 * lw  # conv
        p += 2 * lw * lw / cfg.n_heads  # block-diag gates
    elif kind == "mlstm":
        di = 2 * d
        p += d * 2 * di + di * d  # up/down
        p += 3 * di * di / cfg.n_heads  # q,k,v block-diag
        p += 2 * di * cfg.n_heads + 4 * di
    elif kind == "slstm":
        p += 4 * d * d + 4 * d * d / cfg.n_heads
        p += (4 * d // 3) * d * 3  # geglu ffn
    if kind in ("moe_attn", "mla_moe_attn"):
        moe = cfg.moe
        per_expert = 3 * d * moe.expert_d_ff
        n_live = moe.top_k if active_only else moe.n_experts
        p += n_live * per_expert
        p += d * moe.n_experts  # router
        if moe.n_shared_experts:
            p += 3 * d * (moe.shared_d_ff or
                          moe.n_shared_experts * moe.expert_d_ff)
    elif kind in ("attn", "local_attn", "enc_attn", "dense_attn", "xattn"):
        mult = 3 if cfg.mlp in ("swiglu", "geglu") else 2
        p += mult * d * cfg.d_ff
    elif kind == "mla_attn":
        p += 3 * d * cfg.d_ff
    return p
