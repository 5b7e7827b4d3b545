"""Roofline-term extraction from compiled XLA artifacts.

Every peak comes from one table keyed by the device kind JAX reports
(``jax.devices()[0].device_kind``), each row with its published source; a
device that is not in the table has no roofline and asking for one raises.

terms (seconds, per device — the SPMD module is the per-device program):
  compute    = HLO_FLOPs / peak
  memory     = HLO_bytes / HBM bandwidth
  collective = collective_operand_bytes / per-link ICI bandwidth
"""
from __future__ import annotations

import re
from dataclasses import dataclass, asdict
from typing import Dict, NamedTuple, Optional


class Peaks(NamedTuple):
    flops: float       # bf16 FLOP/s per chip
    hbm_bw: float      # HBM bytes/s per chip
    link_bw: float     # ICI bytes/s per link
    source: str


#: published per-chip peaks, keyed by ``Device.device_kind``
PEAKS = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bw=819e9, link_bw=50e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip (4 links)"),
}

#: the chip the dry-run compiles for (``launch/dryrun.py``)
V5E = "TPU v5 lite"


def device_kind() -> str:
    import jax
    return jax.devices()[0].device_kind


def peaks(kind: Optional[str] = None) -> Peaks:
    """The published peaks of ``kind`` (the first device's by default)."""
    kind = kind or device_kind()
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {kind!r}; "
                         f"known: {sorted(PEAKS)}") from None


def mem_bw() -> float:
    """HBM bandwidth (bytes/s) of the first device — the denominator of
    every achieved-GB/s fraction the benchmarks report."""
    return peaks().hbm_bw


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b(pred|bf16|f16|f32|f64|f8e4m3fn|f8e5m2|"
                       r"[su](?:4|8|16|32|64)|c64|c128)\[([\d,]*)\]")


# --------------------------------------------------------------------------
# streaming-traffic models for the sort/merge benchmarks (DESIGN.md §7.3):
# a merge pass reads and writes every element once, so the minimal traffic
# of a K-run reduction is 2·n·itemsize per pass — the roofline bound a
# measured row is compared against.
# --------------------------------------------------------------------------

def stream_bytes(n_elems: int, itemsize: int, passes: int = 1) -> int:
    """Bytes moved by ``passes`` read+write streaming passes over the data."""
    return 2 * n_elems * itemsize * passes


def merge_tree_passes(n_runs: int, levels_per_pass: int = 1) -> int:
    """HBM round trips to reduce ``n_runs`` sorted runs: ``ceil(log2 K)``
    tree levels, ``levels_per_pass`` of them fused per pass (the
    MergeSchedule dof). One-shot executors (``xla``) count as one pass."""
    import math
    if n_runs <= 1:
        return 0
    levels = math.ceil(math.log2(n_runs))
    return -(-levels // max(levels_per_pass, 1))


def sort_stream_bytes(n: int, itemsize: int, chunk: int,
                      levels_per_pass: int = 1) -> int:
    """Minimal streaming traffic of a two-level sort: one chunk-sort pass
    plus the merge-tree reduction of ``n/chunk`` runs."""
    runs = max(-(-n // max(chunk, 1)), 1)
    return stream_bytes(n, itemsize,
                        1 + merge_tree_passes(runs, levels_per_pass))


def external_passes(n_runs: int, fan_in: int) -> int:
    """Phase-2 HBM round trips of the out-of-core sort: merging ``fan_in``
    runs per group per pass, ``n_runs`` reduce in ``ceil(log_fan_in)``
    streamed passes (``engine.external_sort``, DESIGN.md §8)."""
    f = max(fan_in, 2)
    passes, r = 0, max(n_runs, 1)
    while r > 1:                  # exact integer ceil(log_f): mirrors the
        r = -(-r // f)            # driver's per-pass ceil(runs / fan_in)
        passes += 1
    return passes


def external_sort_bytes(n: int, itemsize: int, tile: int,
                        fan_in: int) -> int:
    """Minimal streaming traffic of the two-phase out-of-core sort: one
    run-formation pass over the data plus ``external_passes`` streamed
    run-merge passes — the traffic model the external-sort benchmark rows
    are priced against."""
    runs = max(-(-n // max(tile, 1)), 1)
    return stream_bytes(n, itemsize, 1 + external_passes(runs, fan_in))


def moe_route_bytes(T: int, E: int, k: int, fused: bool = True) -> int:
    """Minimal streaming traffic of MoE routing for a chunk of ``T`` tokens,
    ``k`` active of ``E`` experts (all lanes f32/int32 = 4 bytes).

    ``fused`` (``engine.moe_route`` megakernel, DESIGN.md §9): the logits are
    read once and only the six routed lanes (experts, tokens, perm, weights,
    slabs, keep) are written — nothing between softmax and the capacity cut
    touches HBM. Unfused: every stage round-trips its intermediates — top-k
    values+indices, the softmax'd weights, the three sorted lanes, and the
    rank/keep/slab scan each cost a read+write — the traffic the fusion
    deletes, and the denominator of its roofline speedup claim."""
    lane = T * k * 4
    logits = T * E * 4
    out_lanes = 6 * lane
    if fused:
        return logits + out_lanes
    return (logits + 2 * lane          # top-k: read logits, write vals+idx
            + 2 * lane                 # softmax over the top-k values
            + 2 * 3 * lane             # stable KV sort: 3 lanes in + out
            + 2 * 3 * lane             # rank scan + keep + slab select
            + out_lanes)


def moe_dispatch_bytes(T: int, E: int, k: int, d: int, cap: int,
                       itemsize: int = 4, fused: bool = True) -> int:
    """Streaming-traffic model of one full dispatch: route, scatter tokens
    into the (E, cap, d) slabs, stream the slabs through the experts once
    (read in, write out), and combine back to (T, d) — the price a measured
    ``moe_apply_*`` row is compared against."""
    io = 2 * T * d * itemsize          # read x, write y
    slab = E * cap * d * itemsize
    return io + 4 * slab + moe_route_bytes(T, E, k, fused)


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Operand bytes per collective type, loop-trip-count aware."""
    from repro.launch.hlo_cost import analyze_hlo
    cost = analyze_hlo(hlo_text)
    out = {k: int(cost.coll.get(k, 0)) for k in _COLLECTIVES}
    return out


@dataclass
class Roofline:
    flops: float
    hbm_bytes: float
    coll_bytes: float
    t_compute: float
    t_memory: float
    t_collective: float
    bottleneck: str
    model_flops: float
    useful_ratio: float
    mem_per_device_gb: float

    def to_dict(self):
        return asdict(self)


def analyse(compiled, *, model_flops: float, n_chips: int) -> Roofline:
    # NOTE: compiled.cost_analysis() counts while bodies ONCE (verified), so
    # we use the trip-count-aware HLO analyzer for scan-over-layers programs.
    from repro.launch.hlo_cost import analyze_hlo
    cost = analyze_hlo(compiled.as_text())
    flops = cost.flops
    hbm = cost.bytes
    coll = cost.coll_total
    pk = peaks(V5E)
    t_c = flops / pk.flops
    t_m = hbm / pk.hbm_bw
    t_x = coll / pk.link_bw
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    bottleneck = max(terms, key=terms.get)
    mem = compiled.memory_analysis()
    mem_gb = 0.0
    try:
        mem_gb = (mem.argument_size_in_bytes + mem.output_size_in_bytes +
                  mem.temp_size_in_bytes) / 1e9
    except Exception:
        pass
    per_dev_model_flops = model_flops / n_chips
    return Roofline(flops, hbm, coll, t_c, t_m, t_x, bottleneck,
                    model_flops,
                    per_dev_model_flops / flops if flops else 0.0,
                    mem_gb)


def model_flops_estimate(cfg, shape_kind: str, seq_len: int,
                         global_batch: int) -> float:
    """MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE); decode D = batch."""
    n_active = param_count_active(cfg)
    if shape_kind == "train":
        tokens = seq_len * global_batch
        return 6.0 * n_active * tokens
    if shape_kind == "prefill":
        tokens = seq_len * global_batch
        return 2.0 * n_active * tokens          # forward only
    tokens = global_batch                        # one token per request
    return 2.0 * n_active * tokens


def param_count_active(cfg) -> float:
    """Active-parameter count (MoE counts top-k experts only)."""
    d, L, V = cfg.d_model, cfg.n_layers, cfg.vocab_size
    hd, H, K = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    attn = d * hd * (H + 2 * K) + H * hd * d
    if cfg.family == "moe":
        f = cfg.moe_d_ff or cfg.d_ff
        ffn = 3 * d * f * cfg.n_experts_active + d * cfg.n_experts
    else:
        ffn = 3 * d * cfg.d_ff
    if cfg.arch_kind == "mamba_hybrid":
        d_in = cfg.ssm_expand * d
        N = cfg.ssm_state
        mamba = d * (2 * d_in + 2 * N + d_in // cfg.ssm_head_dim) + d_in * d
        n_attn = L // cfg.hybrid_attn_every
        return L * mamba + n_attn * (attn + ffn) + V * d
    if cfg.arch_kind == "xlstm":
        mlstm = 3 * d * H * hd + d * 2 * H + H * hd * d
        slstm = 8 * d * d + d * d
        k = cfg.slstm_every
        ng = L // k
        return ng * ((k - 1) * mlstm + slstm) + V * d
    if cfg.arch_kind == "encdec":
        enc = (cfg.n_encoder_layers or L) * (attn + ffn)
        cross = L * attn
        return enc + L * (attn + ffn) + cross + V * d
    return L * (attn + ffn) + V * d
