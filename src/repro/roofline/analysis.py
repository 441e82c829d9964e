"""Roofline analysis from compiled HLO (no hardware required).

Three terms per (arch x shape x mesh) cell, all PER-CHIP:

  compute term    = dot_FLOPs_local / peak_FLOPs            [s]
  memory term     = HBM_bytes_local / HBM_bw                [s]
  collective term = wire_bytes_local / (links * link_bw)    [s]

Sources:
  * ``compiled.as_text()`` — post-SPMD HLO with LOCAL (per-device) shapes.
    We parse every ``dot`` op (operand shapes resolved through a per-
    computation symbol table) and every collective, and multiply ops inside
    while-loop bodies by the loop trip count, which XLA records as
    ``backend_config={"known_trip_count":{"n":N}}``.  This fixes the
    known undercount of ``cost_analysis()`` (scan bodies counted once —
    verified empirically: a 10-iteration scan reports 10x fewer FLOPs).
  * Memory term: analytic traffic model (params + activation boundaries +
    KV/state cache; see ``_memory_bytes``) — cost_analysis byte counts
    share the while-loop undercount and on CPU include host copies, so the
    analytic model is the per-chip HBM estimate we trust; both are
    reported.

Hardware constants (TPU v5e-class, per the assignment):
  197 TFLOP/s bf16 per chip | 819 GB/s HBM | ~50 GB/s/link ICI.

Wire-byte convention: all-reduce counts 2x payload (reduce-scatter +
all-gather of a ring), others 1x; payload is the op's local result bytes.
"""
from __future__ import annotations

import re
from typing import Optional

import numpy as np

__all__ = [
    "parse_hlo",
    "analyze_compiled",
    "HW_PEAK",
    "LayerBound",
    "PerfModel",
]

HW_PEAK = {
    "flops_bf16": 197e12,   # per chip
    "ops_int8": 394e12,     # int8 MXU ops/s (2x the bf16 MAC rate)
    "hbm_gbps": 819e9,      # bytes/s
    "ici_link_gbps": 50e9,  # bytes/s per link
    "ici_links": 1,         # conservative single-link budget per chip
    "hbm_gib": 16.0,        # v5e HBM capacity
}

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "token": 0, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_HDR = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s+\(.*\)\s*->.*\{")
_OP_RE = re.compile(r"^\s*(ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\((.*)")
_TRIP_RE = re.compile(r'known_trip_count[":{\s]+n["\s:]+"?(\d+)')
_CALLED_RE = re.compile(r"(?:body|calls|to_apply|condition)=%?([\w\.\-]+)")

COLLECTIVES = {
    "all-reduce": 2.0,
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def _shape_bytes(type_str: str) -> int:
    """Bytes of a result type, handling tuples."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.groups()
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_dims(type_str: str):
    m = _SHAPE_RE.search(type_str)
    if not m:
        return None
    dims = m.group(2)
    return [int(d) for d in dims.split(",")] if dims else []


def parse_hlo(text: str) -> dict:
    """Parse compiled HLO text -> per-chip dot FLOPs + collective bytes."""
    # ---- split into computations ----------------------------------------
    comps: dict = {}
    cur = None
    for line in text.splitlines():
        hdr = _COMP_HDR.match(line)
        if hdr:
            cur = hdr.group(2)
            comps[cur] = {"ops": [], "symtab": {}}
            continue
        if cur is None:
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _OP_RE.match(line)
        if not m:
            continue
        _, name, rtype, opkind, rest = m.groups()
        comps[cur]["symtab"][name] = rtype
        comps[cur]["ops"].append((name, rtype, opkind, rest, line))

    # ---- build caller->callee multipliers --------------------------------
    mult = {c: 1.0 for c in comps}
    # Repeated relaxation handles nesting (child mult = parent mult * trip).
    edges = []  # (parent, child, factor)
    for cname, comp in comps.items():
        for name, rtype, opkind, rest, line in comp["ops"]:
            factor = 1.0
            if opkind == "while":
                t = _TRIP_RE.search(line)
                if t:
                    factor = float(t.group(1))
            for callee in _CALLED_RE.findall(line):
                if callee in comps:
                    edges.append((cname, callee, factor if opkind == "while" else 1.0))
    for _ in range(12):  # fixpoint over nesting depth
        changed = False
        for parent, child, factor in edges:
            want = mult[parent] * factor
            if want > mult[child]:
                mult[child] = want
                changed = True
        if not changed:
            break

    # ---- dots + collectives ----------------------------------------------
    flops = 0.0
    coll_bytes = 0.0
    coll_by_kind: dict = {}
    dots = []
    colls = []
    for cname, comp in comps.items():
        m_ = mult[cname]
        symtab = comp["symtab"]
        for name, rtype, opkind, rest, line in comp["ops"]:
            if opkind == "dot":
                out_dims = _shape_dims(rtype) or []
                out_n = float(np.prod(out_dims)) if out_dims else 1.0
                # contraction size from lhs operand shape.  Depending on the
                # XLA version the operand is printed inline-typed
                # ("dot(f32[64,512]{1,0} %param, ...)") or bare ("dot(%param,
                # ...)"); read the inline type first, else the symbol table.
                cdims_m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", line)
                lhs_dims = None
                if _SHAPE_RE.match(rest):
                    lhs_dims = _shape_dims(rest.split(" ")[0])
                else:
                    lhs_m = re.match(r"%?([\w\.\-]+)", rest)
                    if lhs_m and lhs_m.group(1) in symtab:
                        lhs_dims = _shape_dims(symtab[lhs_m.group(1)])
                csize = 1.0
                if cdims_m and lhs_dims:
                    for ci in cdims_m.group(1).split(","):
                        if ci and int(ci) < len(lhs_dims):
                            csize *= lhs_dims[int(ci)]
                f = 2.0 * out_n * csize * m_
                flops += f
                dots.append({"comp": cname, "out": rtype, "flops": f})
            elif opkind in COLLECTIVES:
                b = _shape_bytes(rtype) * COLLECTIVES[opkind] * m_
                coll_bytes += b
                coll_by_kind[opkind] = coll_by_kind.get(opkind, 0.0) + b
                meta = re.search(r'op_name="([^"]*)"', line)
                colls.append({
                    "comp": cname, "kind": opkind, "out": rtype.split("{")[0],
                    "bytes": b, "mult": m_,
                    "op_name": meta.group(1) if meta else "",
                })
    dots.sort(key=lambda d: -d["flops"])
    colls.sort(key=lambda c: -c["bytes"])
    return {
        "dot_flops": flops,
        "collective_bytes": coll_bytes,
        "collective_by_kind": coll_by_kind,
        "top_dots": dots[:8],
        "top_collectives": colls[:10],
        "all_collectives": colls,
        "n_computations": len(comps),
    }


# ---------------------------------------------------------------------------
# Analytic per-chip HBM traffic model (see module docstring).
# ---------------------------------------------------------------------------
def _memory_bytes(cfg, shape, n_chips: int, model_axis: int) -> float:
    n_params = cfg.param_count()
    d = cfg.d_model
    b_local = max(shape.global_batch // max(n_chips // model_axis, 1), 1)
    if shape.kind == "train":
        # fp32 params sharded over all chips (FSDP x TP): fwd read + bwd read
        # + grad write + AdamW (read p,mu,nu + write p,mu,nu) = 9 passes.
        param_traffic = 9.0 * 4.0 * n_params / n_chips
        # activation boundaries: save + reload per layer (remat recomputes
        # interior): 2 passes of (B_local, S, D) bf16 per layer.
        act = 4.0 * b_local * shape.seq_len * d * 2.0 * cfg.n_layers
        return param_traffic + act
    if shape.kind == "prefill":
        param_traffic = 4.0 * n_params / n_chips
        act = 2.0 * b_local * shape.seq_len * d * 2.0 * cfg.n_layers
        # KV cache write
        kv = 2.0 * b_local * shape.seq_len * cfg.n_kv_heads * cfg.head_dim_ * 2.0 \
            * cfg.n_layers / model_axis
        return param_traffic + act + kv
    # decode: full param read + full cache read per token.
    param_traffic = 4.0 * n_params / n_chips
    if cfg.family == "ssm":
        state = cfg.n_layers * b_local * (d // 64) * 64 * 64 * 4.0 * 2.0
        return param_traffic + state
    if cfg.family == "hybrid":
        n_attn = cfg.n_layers // (cfg.attn_period or 6)
        n_mamba = cfg.n_layers - n_attn
        state = n_mamba * b_local * (cfg.d_inner // 64) * cfg.ssm_state * 64 * 4.0 * 2.0
        kv = 2.0 * b_local * shape.seq_len * cfg.n_kv_heads * cfg.head_dim_ * 2.0 \
            * n_attn / model_axis
        return param_traffic + state + kv
    kv = 2.0 * b_local * shape.seq_len * cfg.n_kv_heads * cfg.head_dim_ * 2.0 \
        * cfg.n_layers / model_axis
    return param_traffic + kv


def analyze_compiled(compiled, cfg, shape, mesh_devices: int, model_axis: int,
                     bf16_wire: bool = False) -> dict:
    parsed = parse_hlo(compiled.as_text())
    peak = HW_PEAK
    compute_s = parsed["dot_flops"] / peak["flops_bf16"]
    mem_bytes = _memory_bytes(cfg, shape, mesh_devices, model_axis)
    memory_s = mem_bytes / peak["hbm_gbps"]
    coll_bytes = parsed["collective_bytes"]
    if bf16_wire:
        # TPU-dtype normalization: the CPU backend's FloatNormalization pass
        # runs BEFORE SPMD partitioning and upcasts every bf16 dot to f32,
        # so dot-adjacent collectives (param all-gathers, partial-sum and
        # gradient reductions) appear as 4-byte words in the compiled HLO
        # even when params/activations are bf16.  On the TPU target those
        # dots are native bf16 and the same collectives move 2-byte words
        # (MaxText-observed behavior).  Halve dot-attributed collectives.
        dot_bytes = sum(
            c["bytes"] for c in parsed["all_collectives"]
            if "dot_general" in c["op_name"] and "f32" in c["out"]
        )
        coll_bytes = coll_bytes - dot_bytes / 2.0
    coll_s = coll_bytes / (peak["ici_links"] * peak["ici_link_gbps"])

    terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
    bottleneck = max(terms, key=terms.get)

    # MODEL_FLOPS: 6*N*D with N = (active) params, D = tokens processed.
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    n_active = cfg.active_param_count()
    model_flops_global = (6.0 if shape.kind == "train" else 2.0) * n_active * tokens
    model_flops_local = model_flops_global / mesh_devices
    hlo = parsed["dot_flops"]
    useful = model_flops_local / hlo if hlo else 0.0

    return {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": coll_s,
        "bottleneck": bottleneck,
        "dot_flops_local": parsed["dot_flops"],
        "collective_bytes_local": parsed["collective_bytes"],
        "collective_by_kind": {k: round(v) for k, v in parsed["collective_by_kind"].items()},
        "memory_bytes_local": mem_bytes,
        "model_flops_local": model_flops_local,
        "useful_flops_ratio": useful,
        "step_time_lower_bound_s": max(terms.values()),
        "roofline_fraction": useful * (compute_s / max(terms.values())) if hlo else 0.0,
        "top_dots": parsed["top_dots"][:5],
        "top_collectives": parsed["top_collectives"][:8],
    }


# ---------------------------------------------------------------------------
# SNN kernel performance model: analytic wall-time bounds for the fused
# per-timestep kernel (kernels.fused_lif_gemm_int).
#
# A thin, explicit wrapper in the style of DaCe's RooflineModel: peaks in,
# (bytes-moved, MACs-at-sparsity) per layer, bound = max(compute, memory).
# The bound is an *ideal-hardware* floor — interpret-mode CPU runs sit far
# above it — so the CI perf gate (tools/check_bench.py) tracks the RATIO
# measured_wall / bound against the committed baseline's ratio: the bound
# normalizes shape/sparsity/tiling differences out of the wall clock, and
# a regression in the ratio means the implementation got slower relative
# to what the dataflow says it should cost.
# ---------------------------------------------------------------------------
import dataclasses as _dataclasses


def _ceil_to(x: int, b: int) -> int:
    return -(-x // b) * b


@_dataclasses.dataclass(frozen=True)
class LayerBound:
    """Roofline bound for one weight layer over a whole event chunk."""

    rows: int                # GEMM M (batch x output positions)
    fan_in: int              # GEMM K
    channels: int            # GEMM N
    timesteps: int
    macs: float              # MACs actually issued (after tile skipping)
    bytes_moved: float       # HBM bytes under the block tiling
    compute_s: float
    memory_s: float

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s)

    @property
    def bottleneck(self) -> str:
        return "compute" if self.compute_s >= self.memory_s else "memory"


class PerfModel:
    """Analytic roofline for the fused SNN hot path.

    ``peaks`` defaults to :data:`HW_PEAK`; pass overrides to model other
    parts (``{"ops_int8": ..., "hbm_gbps": ...}``).  All methods are pure
    and deterministic — the same (shape, precision, tiling, sparsity)
    always prices to the same bound, which is what lets benchmarks commit
    measured/bound ratios as a regression baseline.
    """

    def __init__(self, peaks: Optional[dict] = None):
        self.peaks = dict(HW_PEAK)
        if peaks:
            self.peaks.update(peaks)

    def layer_bound(
        self,
        rows: int,
        fan_in: int,
        channels: int,
        *,
        timesteps: int,
        nonzero_tile_frac: float = 1.0,
        block: tuple = (128, 128, 128),
    ) -> LayerBound:
        """Bound one layer's chunk, one kernel call per timestep.

        ``nonzero_tile_frac`` is the fraction of (bm x bk) spike tiles
        that carry at least one spike (measure it with
        ``kernels.spike_tile_bitmap``); it scales the MAC term — the
        block-sparsity lever — while the byte terms keep the dense spike
        stream (every spike tile is read either way; weight traffic is
        decided by tiling, not sparsity).

        Byte model of ``fused_lif_gemm_int`` over ``T`` timesteps:
          * weights: the (K_p x N_p) int8 matrix streams once per m-tile
            per call — ``gm * K_p * N_p * T``;
          * spikes: each (bm, bk) int8 tile is read once per n-tile —
            ``T * R_p * K_p * gn``;
          * Vmem carry: the (bm, bn) int32 tile reads once per (i, j)
            per call;
          * outputs: the (M, N) int32 Vmem and spikes write once each a
            call.
        """
        bm, bn, bk = block
        r_p, k_p, n_p = _ceil_to(rows, bm), _ceil_to(fan_in, bk), \
            _ceil_to(channels, bn)
        gm, gn = r_p // bm, n_p // bn

        w_bytes = float(gm * k_p * n_p) * timesteps
        s_bytes = float(timesteps * r_p * k_p) * gn
        v_bytes = 4.0 * r_p * n_p * timesteps
        out_bytes = 2.0 * 4.0 * timesteps * r_p * n_p
        bytes_moved = w_bytes + s_bytes + v_bytes + out_bytes

        macs = float(rows) * fan_in * channels * timesteps \
            * max(0.0, min(1.0, nonzero_tile_frac))
        compute_s = 2.0 * macs / self.peaks["ops_int8"]
        memory_s = bytes_moved / self.peaks["hbm_gbps"]
        return LayerBound(
            rows=rows, fan_in=fan_in, channels=channels,
            timesteps=timesteps, macs=macs, bytes_moved=bytes_moved,
            compute_s=compute_s, memory_s=memory_s,
        )

    def network_bound(
        self,
        spec,
        *,
        batch: int = 1,
        timesteps: Optional[int] = None,
        block: tuple = (128, 128, 128),
        nonzero_tile_fracs=None,
    ) -> dict:
        """Aggregate per-layer bounds over an ``SNNSpec``.

        ``nonzero_tile_fracs`` is a per-weight-layer list (default: dense,
        1.0).  Returns per-layer :class:`LayerBound` rows plus total
        bytes/MACs and the summed wall-time bound in seconds and
        microseconds.
        """
        shapes = spec.layer_shapes()
        timesteps = spec.timesteps if timesteps is None else timesteps
        if nonzero_tile_fracs is None:
            nonzero_tile_fracs = [1.0] * len(shapes)
        layers = []
        for sh, frac in zip(shapes, nonzero_tile_fracs):
            rows = batch * sh.out_positions if sh.kind == "conv" else batch
            layers.append(self.layer_bound(
                rows, sh.fan_in, sh.out_channels,
                timesteps=timesteps, nonzero_tile_frac=frac, block=block,
            ))
        bound_s = sum(lb.bound_s for lb in layers)
        return {
            "layers": layers,
            "bytes_moved": sum(lb.bytes_moved for lb in layers),
            "macs": sum(lb.macs for lb in layers),
            "compute_s": sum(lb.compute_s for lb in layers),
            "memory_s": sum(lb.memory_s for lb in layers),
            "bound_s": bound_s,
            "bound_us": bound_s * 1e6,
        }
