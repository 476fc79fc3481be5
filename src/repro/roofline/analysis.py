"""Three-term roofline analysis from AOT-compiled artifacts.

    compute term    = HLO_FLOPs_per_device / peak_FLOPs_per_chip
    memory term     = HLO_bytes_per_device / HBM_bandwidth_per_chip
    collective term = collective_bytes_per_device / ICI_link_bandwidth

The compiled module is the per-device SPMD program, so cost_analysis()
already reports per-device FLOPs/bytes; equivalently the spec's
"global / (chips x peak)" formulation.  collective_bytes is not in
cost_analysis — we parse the optimized HLO and sum operand sizes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute.

The peaks come from ``PEAKS``, keyed by ``jax.Device.device_kind``; a
device kind that is not in the table is an error, never a default.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field


@dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks: FLOP/s (bf16), HBM bytes/s, and bytes/s
    of one ICI link (the conservative single-link collective term)."""

    flops: float
    hbm_bw: float
    link_bw: float
    source: str


PEAKS = {
    "TPU v5 lite": ChipPeaks(
        flops=197e12, hbm_bw=819e9, link_bw=50e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s; ICI 1,600 Gbit/s per chip, taken "
               "as one 50 GB/s link (EXPERIMENTS.md §Roofline)"),
}


def peaks_for(device_kind: str) -> ChipPeaks:
    """The peaks of ``device_kind``; an unknown kind raises ``KeyError``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" known kinds: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# instruction definition:  %name = bf16[8,4096]{1,0} op-name(...)
_DEF_RE = re.compile(
    r"%([\w.\-]+)\s*=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^\s]*)\s+([\w\-]+)")
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def _shape_bytes(dtype: str, dims: str) -> int:
    nb = _DTYPE_BYTES.get(dtype)
    if nb is None:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * nb


def _type_bytes(type_str: str) -> int:
    return sum(_shape_bytes(d, dims) for d, dims in
               _SHAPE_RE.findall(type_str))


def collective_bytes(hlo_text: str) -> dict:
    """Wire bytes per device, per collective kind, from optimized HLO.

    Operand refs in optimized HLO don't carry types, so a first pass builds
    a symbol table %name -> result bytes; the second pass applies the usual
    ring-algorithm wire-byte estimates:

        all-gather:          out - in          (per device)
        reduce-scatter:      in - out
        all-reduce:          2 * in * (g-1)/g  ~= 2 * in
        all-to-all:          in * (g-1)/g      ~= in
        collective-permute:  in

    Collectives inside while bodies appear once in the text — the dry-run
    lowers scans fully unrolled so the static sum is the true per-step sum.
    """
    sizes: dict[str, int] = {}
    insts = []
    for line in hlo_text.splitlines():
        s = line.strip()
        m = _DEF_RE.search(s)
        if not m:
            continue
        name, type_str, op = m.group(1), m.group(2), m.group(3)
        sizes[name] = _type_bytes(type_str)
        base_op = op.rstrip("0123456789.")
        if base_op in _COLLECTIVES:
            insts.append((s, name, type_str, base_op))

    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for s, name, type_str, op in insts:
        kind = op if op in _COLLECTIVES else op.rstrip("0123456789.")
        paren = s.find("(", s.find(kind))
        if paren < 0:
            continue
        depth, end = 0, paren
        for i in range(paren, len(s)):
            if s[i] == "(":
                depth += 1
            elif s[i] == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        in_bytes = sum(sizes.get(o, 0)
                       for o in _OPERAND_RE.findall(s[paren:end]))
        out_bytes = _type_bytes(type_str)
        if kind == "all-gather":
            b = max(out_bytes - in_bytes, 0)
        elif kind == "reduce-scatter":
            b = max(in_bytes - out_bytes, 0)
        elif kind == "all-reduce":
            b = 2 * in_bytes
        elif kind == "all-to-all":
            b = in_bytes
        else:                        # collective-permute
            b = in_bytes
        out[kind] += b
        counts[kind] += 1
    return {"per_kind": out, "counts": counts, "total": sum(out.values())}


@dataclass
class Roofline:
    flops: float
    bytes_accessed: float
    coll_bytes: float
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: float = 0.0
    useful_ratio: float = 0.0
    coll_detail: dict = field(default_factory=dict)

    def as_dict(self):
        return dict(flops=self.flops, bytes_accessed=self.bytes_accessed,
                    coll_bytes=self.coll_bytes, compute_s=self.compute_s,
                    memory_s=self.memory_s, collective_s=self.collective_s,
                    bottleneck=self.bottleneck, model_flops=self.model_flops,
                    useful_ratio=self.useful_ratio,
                    coll_detail=self.coll_detail)


def analyze(compiled, *, n_chips: int, device_kind: str,
            model_flops_global: float = 0.0,
            hlo_text: str | None = None) -> Roofline:
    """Roofline terms of ``compiled`` against the peaks of
    ``device_kind``: the chip the program was compiled for."""
    peaks = peaks_for(device_kind)
    cost = compiled.cost_analysis()
    if isinstance(cost, list):           # older jax returns [dict]
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = collective_bytes(text)
    compute_s = flops / peaks.flops
    memory_s = nbytes / peaks.hbm_bw
    collective_s = coll["total"] / peaks.link_bw
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    useful = 0.0
    if model_flops_global and flops:
        useful = model_flops_global / (flops * n_chips)
    return Roofline(flops=flops, bytes_accessed=nbytes,
                    coll_bytes=coll["total"], compute_s=compute_s,
                    memory_s=memory_s, collective_s=collective_s,
                    bottleneck=bottleneck, model_flops=model_flops_global,
                    useful_ratio=useful, coll_detail=coll)


def memory_summary(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:                          # backend-dependent
        return {"error": str(e)}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    if out:
        live = out.get("argument_size_in_bytes", 0) + \
            out.get("temp_size_in_bytes", 0) + \
            out.get("output_size_in_bytes", 0) - \
            out.get("alias_size_in_bytes", 0)
        out["approx_peak_bytes_per_device"] = live
    return out
