"""Compiled-HLO analysis: collective bytes, memory, roofline terms.

``cost_analysis``/``memory_analysis`` give FLOPs and HBM traffic of the
per-device SPMD module; collective traffic is not in cost_analysis, so we
parse the compiled HLO text and sum operand bytes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute,
classifying each op by the slowest link its replica groups cross
(intra-pod ICI vs inter-pod DCI for the (2,16,16) production mesh).

Hardware: :data:`PEAKS`, published per-chip peaks keyed by JAX's
``device_kind`` (a kind not in the table is a ``KeyError``, never a
default).
:data:`HW` is the chip the dry runs, drift reports and the modeled
autotuner price against (TPU v5e).
"""
from __future__ import annotations

import dataclasses
import re

__all__ = ["analyze_collectives", "roofline", "HW", "PEAKS"]


@dataclasses.dataclass(frozen=True)
class Hardware:
    peak_flops: float  # bf16 FLOP/s per chip
    hbm_bw: float  # HBM bytes/s per chip
    hbm_bytes: float  # HBM capacity per chip
    ici_bw: float  # inter-chip bytes/s per link
    dci_bw: float  # bytes/s per chip across pods (assumption)


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of inter-chip
# interconnect (four links, 50 GB/s each).  DCI is not published for a
# single host; it is modeled at 1/4 of a link.
PEAKS = {
    "TPU v5 lite": Hardware(
        peak_flops=197e12, hbm_bw=819e9, hbm_bytes=16e9,
        ici_bw=50e9, dci_bw=12.5e9,
    ),
}


HW = PEAKS["TPU v5 lite"]  # the modeled target chip

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_COLL_RE = re.compile(
    r"=\s*(?:\()?\s*((?:[a-z0-9]+\[[\d,]*\][^ ]*(?:,\s*)?)+)\)?\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{(.*?)\}\s*[,)]")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?"
)
_SRC_TGT_RE = re.compile(r"source_target_pairs=\{(.*?)\}")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_devices(line: str):
    """Extract one representative replica group (list of device ids)."""
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        ng, sz = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        perm = (
            [int(x) for x in m.group(4).split(",")]
            if m.group(4)
            else list(range(len(dims)))
        )
        # iota list: devices arranged in `dims`, transposed by `perm`,
        # reshaped to [ng, sz]; reconstruct the full table.
        import numpy as np

        ids = np.arange(int(np.prod(dims))).reshape(dims).transpose(perm)
        return ids.reshape(ng, sz).tolist()
    m = _GROUPS_RE.search(line)
    if m:
        groups = []
        for grp in re.findall(r"\{([\d,\s]*)\}", "{" + m.group(1) + "}"):
            if grp.strip():
                groups.append([int(x) for x in grp.split(",")])
        return groups or None
    m = _SRC_TGT_RE.search(line)
    if m:
        pairs = re.findall(r"\{(\d+),(\d+)\}", m.group(1))
        return [[int(a), int(b)] for a, b in pairs]
    return None


def _link_class(groups, pod_size: int) -> str:
    if not groups or pod_size <= 0:
        return "ici"
    for g in groups:
        pods = {d // pod_size for d in g}
        if len(pods) > 1:
            return "dci"
    return "ici"


def analyze_collectives(hlo_text: str, pod_size: int = 0) -> dict:
    """Sum per-device collective operand bytes by op kind and link class.

    Result-shape bookkeeping: all-gather results are divided by the group
    size to recover operand bytes; reduce-scatter operands are the result
    times group size (we parse result shapes, which is what HLO prints).
    """
    out = {
        "ops": 0, "ici_bytes": 0, "dci_bytes": 0,
        "by_kind": {},
    }
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_str, kind = m.group(1), m.group(2)
        nbytes = _shape_bytes(shape_str)
        groups = _group_devices(line)
        gsize = max((len(g) for g in groups), default=1) if groups else 1
        if kind == "all-gather":
            operand = nbytes // max(1, gsize)
        elif kind == "reduce-scatter":
            operand = nbytes * gsize
        else:
            operand = nbytes
        cls = _link_class(groups, pod_size)
        out["ops"] += 1
        out[f"{cls}_bytes"] += operand
        k = out["by_kind"].setdefault(kind, {"count": 0, "bytes": 0})
        k["count"] += 1
        k["bytes"] += operand
    return out


def roofline(
    flops_dev: float,
    hbm_bytes_dev: float,
    ici_bytes_dev: float,
    dci_bytes_dev: float,
    useful_flops_dev: float,
    hw: Hardware = HW,
    hbm_bytes_analytic: float | None = None,
) -> dict:
    """Three-term roofline (seconds) + dominant term + MFU-style fraction.

    Two memory terms are reported: ``memory`` uses HLO bytes-accessed (the
    prescribed formula; on the CPU backend it is pre-fusion and therefore
    pessimistic) and ``memory_analytic`` uses the documented min-traffic
    model (params + optimizer + activation saves + logits + caches).  The
    adjusted step time / fraction use the analytic term; both are in the
    tables so the conservative number stays visible.
    """
    t_comp = flops_dev / hw.peak_flops
    t_mem = hbm_bytes_dev / hw.hbm_bw
    t_coll = ici_bytes_dev / hw.ici_bw + dci_bytes_dev / hw.dci_bw
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    t_step = max(terms.values())
    useful_t = useful_flops_dev / hw.peak_flops
    out = {
        **terms,
        "dominant": dominant,
        "t_step": t_step,
        "model_flops_ratio": (
            useful_flops_dev / flops_dev if flops_dev else 0.0
        ),
        "roofline_fraction": useful_t / t_step if t_step else 0.0,
    }
    if hbm_bytes_analytic is not None:
        t_mem_a = hbm_bytes_analytic / hw.hbm_bw
        adj = {"compute": t_comp, "memory": t_mem_a, "collective": t_coll}
        out["memory_analytic"] = t_mem_a
        out["dominant_adj"] = max(adj, key=adj.get)
        out["t_step_adj"] = max(adj.values())
        out["roofline_fraction_adj"] = (
            useful_t / out["t_step_adj"] if out["t_step_adj"] else 0.0
        )
    return out


def analytic_min_hbm(cfg, kind: str, batch: int, seq: int, mesh) -> float:
    """Documented min-HBM-traffic model, bytes per device per step.

    train:   params fwd+bwd reads + AdamW m/v/p read+write (fp32) +
             remat-saved activations (w+r) + layer hot intermediates +
             logits (w+r, fp32)
    prefill: params read + activations + full logits (the unembed is
             applied to every position -- a known inefficiency, see §Perf)
    decode:  params read + full KV/state cache read + 1-slot write
    """
    tp = mesh.shape.get("model", 1)
    dp = max(1, mesh.size // tp)
    p_shard = cfg.param_count() / tp
    toks = batch * seq / dp  # per-device tokens
    d, v = cfg.d_model, cfg.vocab_size

    # per-token per-layer intermediate traffic (bf16), TP-sharded
    per_tok = 0.0
    for k in cfg.pattern_kinds:
        if k in ("attn", "local"):
            hd = cfg.head_dim
            per_tok += (cfg.n_heads + 2 * cfg.n_kv_heads) * hd + d
            if cfg.moe_experts:
                f_act = (
                    cfg.moe_top_k * cfg.moe_d_ff
                    * cfg.moe_capacity_factor
                )
            else:
                f_act = cfg.d_ff * (2 if cfg.gated_mlp else 1)
            per_tok += 2 * f_act + d
        elif k == "rglru":
            r = cfg.rnn_width or d
            per_tok += 4 * r + 2 * cfg.d_ff + d
        elif k == "mlstm":
            per_tok += 6 * cfg.mlstm_expansion * d
        elif k == "slstm":
            per_tok += 8 * d + 2 * int(cfg.slstm_ff_factor * d)
    act_bytes = toks * (per_tok / tp) * 2  # bf16

    if kind == "train":
        # params: fwd read + bwd read (f32) ; opt: r+w of m, v, p (f32)
        param_traffic = p_shard * 4 * (2 + 6)
        remat_saves = toks * d * 2 * cfg.n_layers * 2  # save + reload
        logits = toks * (v / tp) * 4 * 2
        return param_traffic + 3 * act_bytes + remat_saves + logits
    if kind == "prefill":
        return p_shard * 4 + act_bytes + toks * (v / tp) * 4
    # decode: one token; dominated by weights + cache sweep
    cache_bytes = 0.0
    for k in cfg.pattern_kinds:
        if k == "attn":
            cache_bytes += (
                2 * cfg.max_cache * cfg.n_kv_heads * cfg.head_dim * 2
            )
        elif k == "local":
            cache_bytes += (
                2 * cfg.window * cfg.n_kv_heads * cfg.head_dim * 2
            )
        elif k == "mlstm":
            dn = cfg.mlstm_expansion * d
            cache_bytes += (dn // cfg.n_heads) * dn * 4
        elif k == "rglru":
            cache_bytes += (cfg.rnn_width or d) * 4 * cfg.conv_width
        elif k == "slstm":
            cache_bytes += 4 * d * 4
    cache_dev = cache_bytes * batch / dp / max(
        1, tp if cfg.n_kv_heads % tp == 0 else 1
    )
    return p_shard * 4 + cache_dev + act_bytes
