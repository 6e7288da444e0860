"""Roofline-term derivation from compiled XLA artifacts (DESIGN.md §7).

Hardware model: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.

    T_comp = HLO_FLOPs / (chips × 197e12)
    T_mem  = HLO_bytes / (chips × 819e9)
    T_coll = Σ wire_bytes(op) / (chips × 50e9)

SEMANTICS: XLA compiles ONE SPMD partition, so `cost_analysis` FLOPs/bytes
are **per-device** values; the roofline terms are therefore per-device times
directly (no ÷chips).  Collective wire bytes use the ring model, which is
already a per-participating-device quantity:

    all-reduce       2·size·(N−1)/N     (send+receive per device)
    all-gather         size·(N−1)/N     (size = gathered output)
    reduce-scatter     size·(N−1)/N     (size = scattered input)
    all-to-all         size·(N−1)/N
    collective-permute size

We assume one ICI link pair per chip per collective; a torus overlaps axes,
so T_coll is a conservative upper bound.  MODEL_FLOPS is GLOBAL
(6·N_active·tokens train / 2·N_active·tokens decode-prefill); the
per-device useful time is MODEL_FLOPS/(chips·peak) and
flops_ratio = MODEL_FLOPS / (chips·HLO_FLOPs) catches remat/redundancy
waste.
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Any, Dict, Optional

PEAK_FLOPS = 197e12       # bf16 / chip
HBM_BW = 819e9            # bytes/s / chip
ICI_BW = 50e9             # bytes/s / link

# Published per-chip peaks, keyed by `jax.Device.device_kind`.  A kind that
# is not listed has no peak: `device_peak_flops` raises rather than judging
# a utilization against a guess or against a timed host matmul.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": PEAK_FLOPS,
        "hbm_bytes_per_s": HBM_BW,
        "source": "Google Cloud TPU documentation, 'TPU v5e' system "
                  "architecture page (per chip: 197 TFLOP/s bf16, "
                  "819 GB/s HBM)",
    },
}


def device_peak_flops(device_kind: Optional[str] = None) -> tuple:
    """(peak bf16 FLOP/s, source) of `device_kind` (default: the kind of
    `jax.devices()[0]`).  Raises KeyError for a kind with no published
    entry in `DEVICE_PEAKS`."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    entry = DEVICE_PEAKS.get(device_kind)
    if entry is None:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"known kinds: {sorted(DEVICE_PEAKS)}")
    return entry["flops_bf16"], entry["source"]


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"(\w[\w.-]*)\s*=\s*(?:\(([^)]*)\)|(\S+))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", re.I)
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8e4m3fn|f8e5m2|s64|u64|s32|u32|s16|u16|s8|u8|pred|c64|c128)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{(.*?)\}\s*(?:,|$)")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:  # iota format [groups, group_size]
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        first = m.group(1).split("}")[0]
        ids = [t for t in first.replace("{", "").split(",") if t.strip() != ""]
        if ids:
            return len(ids)
    return default


def _wire_bytes(kind: str, out_bytes: int, n: int) -> float:
    frac = (n - 1) / n
    if kind == "all-reduce":
        return 2 * out_bytes * frac
    if kind == "collective-permute":
        return float(out_bytes)
    return out_bytes * frac


# ---------------------------------------------------------------------------
# trip-count-aware HLO analysis
#
# XLA's cost_analysis() (and a naive text scan) counts a while-loop BODY
# once, not × trip count — a scan-over-layers program under-reports by ~L×.
# This analyzer splits the optimized HLO into computations, extracts per-
# computation dot/conv FLOPs, operand+result bytes, and collective wire
# bytes, then expands the call graph from ENTRY:
#   while:        body × known_trip_count
#   conditional:  elementwise MAX over branches (upper bound)
#   call/to_apply: × 1
#   fusion calls=: FLOPs only (fusion internals never touch HBM)
# ---------------------------------------------------------------------------

_COMP_HDR = re.compile(r"^(ENTRY\s+)?%?([\w.\-_]+)\s*\(.*->.*\{\s*$")
_OP_LINE = re.compile(r"^\s+(?:ROOT\s+)?%?[\w.\-_]+\s*=\s*")
_OPNAME = re.compile(r"=\s*(?:\([^)]*\)|[\w\[\],{}]+)\s+([\w\-]+)\(")
_TRIPS = re.compile(r'known_trip_count[^}]*?n["\':\s]+(\d+)')
_WHILE_BODY = re.compile(r"body=%?([\w.\-_]+)")
_COND_TF = re.compile(r"true_computation=%?([\w.\-_]+),\s*false_computation=%?([\w.\-_]+)")
_COND_BR = re.compile(r"branch_computations=\{([^}]*)\}")
_CALLS = re.compile(r"calls=%?([\w.\-_]+)")
_TO_APPLY = re.compile(r"to_apply=%?([\w.\-_]+)")
_DOT_CONTRACT = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_FGC = re.compile(r"feature_group_count=(\d+)")


def _split_computations(text: str):
    comps: Dict[str, list] = {}
    headers: Dict[str, str] = {}
    entry = None
    cur = None
    for line in text.splitlines():
        m = _COMP_HDR.match(line)
        if m:
            cur = m.group(2)
            comps[cur] = []
            headers[cur] = line
            if m.group(1):
                entry = cur
            continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is not None and _OP_LINE.match(line):
            comps[cur].append(line)
    return comps, entry, headers


_PARAM_DECL = re.compile(r"(%?[\w.\-]+):\s")


def _shapes_in(s: str):
    out = []
    for m in _SHAPE_RE.finditer(s):
        dims = [int(x) for x in m.group(2).split(",") if x]
        n = 1
        for d in dims:
            n *= d
        out.append((m.group(1), dims, n * _DTYPE_BYTES[m.group(1)]))
    return out


_REF = re.compile(r"(?<![=\w])%([\w.\-]+)")


def _result_name(line: str):
    lhs = line.split("=", 1)[0].strip()
    return lhs.removeprefix("ROOT").strip().lstrip("%")


def _dot_flops(line: str, symtab: Dict[str, tuple]) -> float:
    rhs = line.split("=", 1)[1]
    res_part, _, rest = rhs.partition(" dot(")
    if not rest:
        return 0.0
    res = _shapes_in(res_part)
    if not res:
        return 0.0
    out_elems = res[0][2] / _DTYPE_BYTES[res[0][0]]
    contract = 1
    mc = _DOT_CONTRACT.search(line)
    operand_refs = _REF.findall(rest.split(")", 1)[0])
    if mc and operand_refs:
        lhs_dims = symtab.get(operand_refs[0], (None, [], 0))[1]
        for i in (int(t) for t in mc.group(1).split(",") if t):
            if i < len(lhs_dims):
                contract *= lhs_dims[i]
    return 2.0 * out_elems * contract


def _conv_flops(line: str) -> float:
    rhs = line.split("=", 1)[1]
    res_part, _, rest = rhs.partition(" convolution(")
    if not rest:
        return 0.0
    res = _shapes_in(res_part)
    ops = _shapes_in(rest)
    if not res or len(ops) < 2:
        return 0.0
    out_elems = res[0][2] / _DTYPE_BYTES[res[0][0]]
    kern_elems = ops[1][2] / _DTYPE_BYTES[ops[1][0]]
    out_ch = res[0][1][-1] if res[0][1] else 1
    mg = _FGC.search(line)
    groups = int(mg.group(1)) if mg else 1
    # per output element: one MAC per kernel element of its group slice
    return 2.0 * out_elems * max(1.0, kern_elems / max(out_ch, 1))


def _param_effective_reads(header: str, lines) -> list:
    """Per-parameter effective HBM read bytes for a fused computation.

    A parameter consumed ONLY by slice-type ops (dynamic-slice/slice/gather)
    is read at the total sliced size, not its full (often L-stacked) size —
    charging the full operand per loop trip inflates weight reads by O(L)."""
    left = header.split("->")[0]
    names = _PARAM_DECL.findall(left)
    shapes = _shapes_in(left)
    out = []
    for i, pname in enumerate(names):
        pname = pname.lstrip("%")
        full = shapes[i][2] if i < len(shapes) else 0
        sliced = 0
        only_sliced = True
        seen = False
        for line in lines:
            dp = line.split("=", 1)[1].split(", metadata=")[0] if "=" in line else line
            if not re.search(r"%?" + re.escape(pname) + r"\b", dp.split("(", 1)[-1]):
                continue
            seen = True
            om = _OPNAME.search(line)
            op = om.group(1).lower() if om else ""
            if op in ("dynamic-slice", "slice", "gather"):
                type_seg = line[line.index("=") + 1 : om.start(1)]
                sliced += sum(b for _, _, b in _shapes_in(type_seg))
            elif op in ("get-tuple-element", "bitcast", "reshape"):
                continue
            else:
                only_sliced = False
                break
        out.append(sliced if (seen and only_sliced and sliced) else full)
    return out


def analyze_hlo(text: str, n_devices: int) -> Dict[str, Any]:
    comps, entry, headers = _split_computations(text)
    eff_reads: Dict[str, list] = {}
    for name, lines in comps.items():
        eff_reads[name] = _param_effective_reads(headers.get(name, ""), lines)
    info: Dict[str, Dict[str, Any]] = {}
    for name, lines in comps.items():
        # symbol table: op result name -> (dtype, dims, bytes) — operands are
        # printed as %refs, so shapes must be resolved via their definitions
        symtab: Dict[str, tuple] = {}
        parsed = []
        for line in lines:
            if "=" not in line:
                continue
            om = _OPNAME.search(line)
            if not om:
                continue
            op = om.group(1).lower()
            type_seg = line[line.index("=") + 1 : om.start(1)]
            res_shapes = _shapes_in(type_seg)
            if res_shapes:
                symtab[_result_name(line)] = res_shapes[0]
            parsed.append((line, op, res_shapes))
        flops = 0.0
        byts = 0.0
        coll: Dict[str, float] = {}
        edges = []        # (child, trips, flops_only)
        branches = []     # list of lists (conditional groups)
        for line, op, res_shapes in parsed:
            data_part = line.split("=", 1)[1].split(", metadata=")[0]
            res_b = sum(b for _, _, b in res_shapes)
            # per-op HBM-traffic model (naive operand+result counting makes a
            # dynamic-slice inside an L-trip loop "read" the whole weight
            # stack L times -> O(L²) phantom bytes):
            if op in ("get-tuple-element", "tuple", "parameter", "constant",
                      "iota", "reshape", "bitcast", "while", "conditional",
                      "call", "after-all", "partition-id", "replica-id"):
                pass                                          # no real traffic
            elif op in ("dynamic-slice", "gather", "slice"):
                byts += 2 * res_b                             # read+write slice
            elif op == "dynamic-update-slice":
                refs = _REF.findall(data_part)
                upd = symtab.get(refs[1], (None, [], res_b))[2] if len(refs) > 1 else res_b
                byts += 2 * upd                               # read+write update
            elif op == "fusion":
                # charge operands at the called computation's EFFECTIVE read
                # (slice-only params read the slice, not the full stack)
                mcall = _CALLS.search(line)
                eff = eff_reads.get(mcall.group(1), []) if mcall else []
                refs = _REF.findall(data_part.split("(", 1)[-1])
                byts += res_b
                for i, ref in enumerate(refs):
                    if ref in symtab:
                        full = symtab[ref][2]
                        byts += min(full, eff[i]) if i < len(eff) else full
            else:
                byts += res_b                                 # result write(s)
                for ref in _REF.findall(data_part):
                    if ref in symtab:
                        byts += symtab[ref][2]                # operand reads
            if op == "dot":
                flops += _dot_flops(line, symtab)
            elif op == "convolution":
                flops += _conv_flops(line)
            elif op in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                        "collective-permute", "all-reduce-start", "all-gather-start",
                        "collective-permute-start"):
                kind = op.replace("-start", "")
                out_b = sum(b for _, _, b in res_shapes)
                n = max(2, _group_size(line, n_devices))
                coll[kind] = coll.get(kind, 0.0) + _wire_bytes(kind, out_b, n)
            if op == "while":
                mb = _WHILE_BODY.search(line)
                mt = _TRIPS.search(line)
                trips = int(mt.group(1)) if mt else 1
                if mb:
                    edges.append((mb.group(1), trips, False))
            elif op == "conditional":
                mtf = _COND_TF.search(line)
                if mtf:
                    branches.append([mtf.group(1), mtf.group(2)])
                else:
                    mbr = _COND_BR.search(line)
                    if mbr:
                        branches.append([b.strip().lstrip("%") for b in mbr.group(1).split(",")])
            elif op == "fusion":
                mc = _CALLS.search(line)
                if mc:
                    edges.append((mc.group(1), 1, True))
            elif op == "call":
                mc = _TO_APPLY.search(line)
                if mc:
                    edges.append((mc.group(1), 1, False))
        info[name] = {"flops": flops, "bytes": byts, "coll": coll,
                      "edges": edges, "branches": branches}

    memo: Dict[str, Any] = {}

    def expand(name: str):
        if name in memo:
            return memo[name]
        node = info.get(name)
        if node is None:
            return (0.0, 0.0, {})
        memo[name] = (node["flops"], node["bytes"], dict(node["coll"]))  # cycle guard
        flops, byts, coll = node["flops"], node["bytes"], dict(node["coll"])
        for child, trips, flops_only in node["edges"]:
            cf, cb, cc = expand(child)
            flops += trips * cf
            if not flops_only:
                byts += trips * cb
                for k, v in cc.items():
                    coll[k] = coll.get(k, 0.0) + trips * v
        for group in node["branches"]:
            results = [expand(b) for b in group]
            flops += max(r[0] for r in results)
            byts += max(r[1] for r in results)
            for k in set().union(*(r[2] for r in results)):
                coll[k] = coll.get(k, 0.0) + max(r[2].get(k, 0.0) for r in results)
        memo[name] = (flops, byts, coll)
        return memo[name]

    flops, byts, coll = expand(entry) if entry else (0.0, 0.0, {})
    return {"flops": flops, "bytes": byts, "bytes_by_kind": coll,
            "total_bytes": sum(coll.values()),
            "count_by_kind": {}, "n_computations": len(comps)}


def parse_collectives(hlo_text: str, n_devices: int) -> Dict[str, Any]:
    """Back-compat wrapper: trip-count-aware collective summary."""
    r = analyze_hlo(hlo_text, n_devices)
    return {"bytes_by_kind": r["bytes_by_kind"], "count_by_kind": r["count_by_kind"],
            "total_bytes": r["total_bytes"]}


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float
    model_flops: float
    t_comp: float
    t_mem: float
    t_coll: float
    sources: Dict[str, str]
    collectives: Dict[str, Any]
    memory_per_device: Optional[float] = None
    notes: str = ""

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_comp, "memory": self.t_mem, "collective": self.t_coll}
        return max(terms, key=terms.get)

    @property
    def step_time_bound(self) -> float:
        return max(self.t_comp, self.t_mem, self.t_coll)

    @property
    def roofline_fraction(self) -> float:
        """useful-compute time / bound  (1.0 = at the roofline)."""
        t_useful = self.model_flops / (self.chips * PEAK_FLOPS)
        return t_useful / max(self.step_time_bound, 1e-30)

    @property
    def flops_ratio(self) -> float:
        """MODEL_FLOPS (global) / compiled FLOPs (global = per-device × chips)."""
        return self.model_flops / max(self.hlo_flops * self.chips, 1.0)

    def to_json(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d.update(dominant=self.dominant, step_time_bound=self.step_time_bound,
                 roofline_fraction=self.roofline_fraction, flops_ratio=self.flops_ratio)
        return d


def analyze(*, arch: str, shape: str, mesh_name: str, chips: int,
            cost: Optional[dict], hlo_text: str, model_flops: float,
            memory_analysis=None, fallback_bytes: float = 0.0,
            notes: str = "") -> RooflineReport:
    # Primary source: the trip-count-aware HLO analyzer (cost_analysis counts
    # while bodies once — useless for scanned programs; its values are kept
    # in the JSON as auxiliary via the caller).
    hlo = analyze_hlo(hlo_text, chips)
    sources = {"flops": "hlo_analyzer", "bytes": "hlo_analyzer"}
    flops = hlo["flops"]
    byts = hlo["bytes"]
    if not flops and cost:
        flops = float(cost.get("flops", 0.0))
        sources["flops"] = "cost_analysis"
    if not flops:
        flops = model_flops / chips
        sources["flops"] = "model_flops_fallback"
    if not byts:
        byts = fallback_bytes
        sources["bytes"] = "analytic_fallback"
    coll = {"bytes_by_kind": hlo["bytes_by_kind"], "count_by_kind": {},
            "total_bytes": hlo["total_bytes"]}

    mem_per_dev = None
    if memory_analysis is not None:
        for attr in ("temp_size_in_bytes", "peak_memory_in_bytes"):
            v = getattr(memory_analysis, attr, None)
            if v:
                args = getattr(memory_analysis, "argument_size_in_bytes", 0) or 0
                mem_per_dev = float(v) + float(args)
                break

    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        hlo_flops=flops, hlo_bytes=byts,
        collective_bytes=coll["total_bytes"], model_flops=model_flops,
        t_comp=flops / PEAK_FLOPS,
        t_mem=byts / HBM_BW,
        t_coll=coll["total_bytes"] / ICI_BW,
        sources=sources, collectives=coll,
        memory_per_device=mem_per_dev, notes=notes,
    )


def format_table(reports) -> str:
    hdr = (f"{'arch':16s} {'shape':12s} {'mesh':10s} {'T_comp(s)':>10s} {'T_mem(s)':>10s} "
           f"{'T_coll(s)':>10s} {'bound':>10s} {'dominant':>10s} {'MF/HLO':>7s} {'roofline%':>9s}")
    rows = [hdr, "-" * len(hdr)]
    for r in reports:
        rows.append(
            f"{r.arch:16s} {r.shape:12s} {r.mesh:10s} {r.t_comp:10.4f} {r.t_mem:10.4f} "
            f"{r.t_coll:10.4f} {r.step_time_bound:10.4f} {r.dominant:>10s} "
            f"{r.flops_ratio:7.3f} {100*r.roofline_fraction:8.1f}%")
    return "\n".join(rows)
