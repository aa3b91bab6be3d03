"""Three-term roofline from a compiled dry-run artifact (no hardware).

    compute term    = HLO_FLOPs_per_device / peak_FLOP/s
    memory term     = HLO_bytes_per_device / HBM_bw
    collective term = wire_bytes_per_device / ICI link bw

**Why not ``compiled.cost_analysis()`` alone**: XLA's HloCostAnalysis counts
a while-loop body ONCE, not times its trip count -- with scan-over-layers
(the only way 72-layer 398B models compile in finite time) that undercounts
every per-layer flop, byte and collective by the layer count.  We therefore
parse the post-SPMD HLO text ourselves:

  * computations are split out; ``while`` instructions are mapped to their
    body/condition computations; the trip count is read from the condition's
    ``s32[] constant(N)``; multipliers propagate through nested loops
    (layer scan x chunked-attention scan).
  * FLOPs: every ``dot`` contributes 2 * output_elems * contraction_size
    (matmuls dominate; elementwise flops are ignored -- documented).
  * HBM bytes: for each top-level instruction in an executed computation,
    operand bytes + result bytes.  Post-fusion, top-level fusion boundaries
    are exactly the tensors that hit HBM; fusion-internal computations are
    excluded.  Parameter/tuple/bitcast/constant bookkeeping is skipped.
  * Collective wire bytes: result bytes (x2 for all-reduce: ring
    reduce-scatter + all-gather), times the loop multiplier.

Shapes in the partitioned module are per-device, so all numbers are
per-chip.  Hardware peaks come from :data:`PEAKS`, keyed by the device kind
jax reports; the dry-run target (a described, not attached, chip) is
:data:`DRYRUN_KIND`.
"""
from __future__ import annotations

import dataclasses
import re


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float             # bf16 FLOP/s per chip
    hbm_bw: float            # HBM bytes/s per chip
    ici_bw: float            # bytes/s per ICI link (one direction)


# Published per-chip peaks (Google Cloud documentation, "TPU v5e":
# 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s ICI over 4 links), keyed by
# ``jax.devices()[i].device_kind``.  A kind missing here is an error, never
# a default: a roofline share against another chip's peaks is meaningless.
PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}
DRYRUN_KIND = "TPU v5 lite"


def peaks_for(device_kind: str) -> Peaks:
    """The published peaks of ``device_kind``; raises for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peak rates for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)})") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")
_WIRE_FACTOR = {"all-gather": 1.0, "all-reduce": 2.0, "reduce-scatter": 1.0,
                "all-to-all": 1.0, "collective-permute": 1.0}

# instruction opcodes that don't move HBM bytes themselves
_SKIP_TRAFFIC = {"parameter", "get-tuple-element", "tuple", "bitcast",
                 "constant", "after-all", "add-dependency", "custom-call"}


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _shape_elems_dims(shape_str: str):
    m = _SHAPE_RE.search(shape_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


@dataclasses.dataclass
class _Instr:
    name: str
    result_type: str
    opcode: str
    args: list          # operand %names
    text: str
    is_root: bool = False


_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*((?:\([^)]*\)|\S+))\s+([\w\-]+)\(([^)]*(?:\([^)]*\))?[^)]*)\)",
)
_COMP_NAME_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)")


def _parse_computations(hlo: str):
    """-> (comps: name -> [raw lines], entry_name).

    A computation header is any top-level (unindented) line ending in '{'
    that contains a '->' return annotation; nested parens in the parameter
    list are common, so we only anchor on the leading name.
    """
    comps = {}
    entry = None
    cur = None
    for line in hlo.splitlines():
        stripped = line.strip()
        if cur is None:
            if (stripped.endswith("{") and "->" in stripped
                    and not line.startswith(" ")):
                m = _COMP_NAME_RE.match(stripped)
                if m:
                    cur = m.group(1)
                    comps[cur] = []
                    if stripped.startswith("ENTRY"):
                        entry = cur
        else:
            if stripped == "}":
                cur = None
            else:
                comps[cur].append(line)
    return comps, entry


def _parse_instrs(lines):
    out = []
    for line in lines:
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, rtype, opcode, args = m.groups()
        arg_names = re.findall(r"%([\w.\-]+)", args)
        out.append(_Instr(name=name, result_type=rtype, opcode=opcode,
                          args=arg_names, text=line,
                          is_root=line.lstrip().startswith("ROOT ")))
    return out


def _dot_flops(instr: _Instr, shapes: dict) -> float:
    """2 * output_elems * contraction_size for a dot instruction."""
    out_dims = _shape_elems_dims(instr.result_type)
    out_elems = 1
    for d in out_dims:
        out_elems *= d
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", instr.text)
    if not m or not instr.args:
        return 2.0 * out_elems          # degenerate; count as elementwise-ish
    lhs_shape = shapes.get(instr.args[0], "")
    lhs_dims = _shape_elems_dims(lhs_shape)
    contract = 1
    for idx in m.group(1).split(","):
        if idx and int(idx) < len(lhs_dims):
            contract *= lhs_dims[int(idx)]
    return 2.0 * out_elems * contract


def hlo_cost(hlo: str) -> dict:
    """Trip-count-aware per-device cost of a partitioned HLO module.

    Returns {"flops", "hbm_bytes", "collectives": {op: {...}},
             "total_wire_bytes", "loops": {body: trip}}.
    """
    comps, entry = _parse_computations(hlo)
    instrs = {name: _parse_instrs(lines) for name, lines in comps.items()}

    # global name -> result type (operand shape lookup)
    shapes = {}
    for ilist in instrs.values():
        for i in ilist:
            shapes[i.name] = i.result_type

    # while loops: body/cond + trip count
    loops = {}        # body comp -> (parent comp, trip)
    for cname, ilist in instrs.items():
        for i in ilist:
            if i.opcode != "while":
                continue
            mc = re.search(r"condition=%?([\w.\-]+)", i.text)
            mb = re.search(r"body=%?([\w.\-]+)", i.text)
            if not (mc and mb):
                continue
            trip = 1
            for line in comps.get(mc.group(1), []):
                for c in re.findall(r"s32\[\]\s+constant\((\d+)\)", line):
                    trip = max(trip, int(c))
            loops[mb.group(1)] = (cname, trip)

    # execution multipliers: ENTRY=1; while body = parent mult * trip
    mult = {entry: 1.0}
    changed = True
    while changed:
        changed = False
        for body, (parent, trip) in loops.items():
            if parent in mult:
                m = mult[parent] * trip
                if mult.get(body) != m:
                    mult[body] = m
                    changed = True

    # --- sliced-operand analysis for fusions -------------------------------
    # Scan-over-layers carries STACKED (layers, ...) buffers; each iteration
    # only dynamic-slices one layer out.  Charging the full stacked operand
    # per iteration would overcount HBM by the layer count, so: if a fusion
    # parameter is used ONLY by dynamic-slice ops inside the called
    # computation, charge the slice bytes instead of the full operand.
    def _fusion_param_costs(called: str):
        """-> {param_index: sliced_bytes or None (= full operand)}."""
        out = {}
        pname_to_idx = {}
        for fi in instrs.get(called, []):
            if fi.opcode == "parameter":
                mi = re.search(r"parameter\((\d+)\)", fi.text)
                if mi:
                    pname_to_idx[fi.name] = int(mi.group(1))
        uses = {}         # param name -> [instrs using it]
        for fi in instrs.get(called, []):
            for a in fi.args:
                if a in pname_to_idx:
                    uses.setdefault(a, []).append(fi)
        for pname, idx in pname_to_idx.items():
            us = uses.get(pname, [])
            if us and all(u.opcode == "dynamic-slice" and u.args
                          and u.args[0] == pname for u in us):
                out[idx] = sum(_shape_bytes(u.result_type) for u in us)
            elif us and all(u.opcode == "dynamic-update-slice" and u.args
                            and u.args[0] == pname for u in us):
                # in-place slot write: charge the update region, not the buffer
                out[idx] = sum(_shape_bytes(shapes.get(u.args[1], ""))
                               for u in us if len(u.args) >= 2)
            elif us and all(u.opcode == "scatter" and u.args
                            and u.args[0] == pname for u in us):
                out[idx] = sum(_shape_bytes(shapes.get(u.args[2], ""))
                               for u in us if len(u.args) >= 3)
            else:
                out[idx] = None
        return out

    _PURE_CONVERT = {"convert", "bitcast", "copy", "reshape", "transpose",
                     "dynamic-slice"}

    def _is_pure_convert_fusion(called: str) -> bool:
        """True if the fusion only moves/retypes data (no arithmetic).

        The CPU backend legalizes bf16 scatter/dot by round-tripping whole
        buffers through f32; a TPU executes bf16 natively and never
        materializes those converts.  Their traffic is tallied separately
        (``legalization_bytes``) so the memory term can be reported raw
        AND TPU-adjusted (DESIGN.md §9).
        """
        ops = [fi.opcode for fi in instrs.get(called, [])
               if fi.opcode != "parameter"]
        return bool(ops) and all(o in _PURE_CONVERT for o in ops) \
            and "convert" in ops

    flops = 0.0
    hbm = 0.0
    legal = 0.0
    colls = {k: {"count": 0, "bytes": 0.0, "wire_bytes": 0.0}
             for k in COLLECTIVE_OPS}
    for cname, m in mult.items():
        for i in instrs.get(cname, []):
            if i.opcode == "dot":
                flops += m * _dot_flops(i, shapes)
            param_costs = {}
            root_dus_bytes = None
            pure_convert = i.opcode == "convert"
            if i.opcode == "fusion":
                # dots inside fusion computations: attribute to the fusion site
                mcall = re.search(r"calls=%?([\w.\-]+)", i.text)
                if mcall:
                    for fi in instrs.get(mcall.group(1), []):
                        if fi.opcode == "dot":
                            flops += m * _dot_flops(fi, shapes)
                        if (fi.is_root and fi.opcode == "dynamic-update-slice"
                                and len(fi.args) >= 2):
                            # in-place slot write at the fusion root
                            root_dus_bytes = _shape_bytes(
                                shapes.get(fi.args[1], ""))
                        if (fi.is_root and fi.opcode == "scatter"
                                and len(fi.args) >= 3):
                            # in-place scatter: charge the updates region
                            root_dus_bytes = _shape_bytes(
                                shapes.get(fi.args[2], ""))
                    param_costs = _fusion_param_costs(mcall.group(1))
                    pure_convert = _is_pure_convert_fusion(mcall.group(1))
            if i.opcode in _SKIP_TRAFFIC or i.opcode == "while":
                continue
            out_b = _shape_bytes(i.result_type)
            if i.opcode == "fusion" and root_dus_bytes is not None:
                out_b = root_dus_bytes
            if i.opcode == "dynamic-slice":
                in_b = out_b                       # reads only the slice
            elif i.opcode == "dynamic-update-slice" and len(i.args) >= 2:
                # in-place: reads the update, writes the slice region
                upd = _shape_bytes(shapes.get(i.args[1], ""))
                in_b, out_b = upd, upd
            elif i.opcode == "scatter" and len(i.args) >= 3:
                # in-place scatter (KV-cache slot write): updates + indices
                upd = (_shape_bytes(shapes.get(i.args[2], ""))
                       + _shape_bytes(shapes.get(i.args[1], "")))
                in_b, out_b = upd, upd
            else:
                in_b = 0
                for ai, a in enumerate(i.args):
                    full = _shape_bytes(shapes.get(a, ""))
                    sliced = param_costs.get(ai)
                    in_b += sliced if sliced is not None else full
            hbm += m * (out_b + in_b)
            if pure_convert:
                legal += m * (out_b + in_b)
            base = i.opcode.removesuffix("-start")
            if base in colls:
                colls[base]["count"] += int(m)
                colls[base]["bytes"] += m * out_b
                colls[base]["wire_bytes"] += m * out_b * _WIRE_FACTOR[base]
    total_wire = sum(v["wire_bytes"] for v in colls.values())
    return {"flops": flops, "hbm_bytes": hbm, "collectives": colls,
            "total_wire_bytes": total_wire, "legalization_bytes": legal,
            "loops": {b: t for b, (_, t) in loops.items()}}


def parse_collectives(hlo_text: str) -> dict:
    """Back-compat: collective summary (trip-count aware)."""
    cost = hlo_cost(hlo_text)
    out = dict(cost["collectives"])
    out["total_wire_bytes"] = cost["total_wire_bytes"]
    return out


@dataclasses.dataclass
class Roofline:
    flops: float              # per device (dot flops, loop-expanded)
    hbm_bytes: float          # per device (fusion-boundary traffic)
    wire_bytes: float         # per device
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float = 0.0  # 6*N_active*D (train) / 2*N_active*D (serve), per device
    useful_ratio: float = 0.0
    xla_flops_raw: float = 0.0   # cost_analysis() as reported (body-once; reference)
    legalization_bytes: float = 0.0   # CPU bf16<->f32 round-trips (absent on TPU)
    memory_s_tpu: float = 0.0         # memory term net of legalization traffic

    @classmethod
    def build(cls, flops, hbm_bytes, wire_bytes, model_flops=0.0,
              xla_flops_raw=0.0, legalization_bytes=0.0,
              device_kind: str = DRYRUN_KIND):
        peak = peaks_for(device_kind)
        c = flops / peak.flops
        m = hbm_bytes / peak.hbm_bw
        n = wire_bytes / peak.ici_bw
        m_tpu = max(hbm_bytes - legalization_bytes, 0.0) / peak.hbm_bw
        dom = max((("compute", c), ("memory", m), ("collective", n)),
                  key=lambda kv: kv[1])[0]
        return cls(flops=flops, hbm_bytes=hbm_bytes, wire_bytes=wire_bytes,
                   compute_s=c, memory_s=m, collective_s=n, dominant=dom,
                   model_flops=model_flops,
                   useful_ratio=(model_flops / flops) if flops else 0.0,
                   xla_flops_raw=xla_flops_raw,
                   legalization_bytes=legalization_bytes,
                   memory_s_tpu=m_tpu)

    def as_dict(self):
        return dataclasses.asdict(self)


def analyze_compiled(compiled, *, model_flops_per_device: float = 0.0,
                     device_kind: str = DRYRUN_KIND) -> Roofline:
    """Roofline of a compiled program against ``device_kind``'s peaks (the
    live device's kind when the program ran on one; raises for a kind
    :data:`PEAKS` does not know)."""
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    parsed = hlo_cost(compiled.as_text())
    return Roofline.build(parsed["flops"], parsed["hbm_bytes"],
                          parsed["total_wire_bytes"],
                          model_flops_per_device,
                          xla_flops_raw=float(cost.get("flops", 0.0)),
                          legalization_bytes=parsed["legalization_bytes"],
                          device_kind=device_kind)


def model_flops(cfg, n_tokens: int, *, train: bool) -> float:
    """6*N_active*D for training, 2*N_active*D for inference (global)."""
    n = cfg.active_param_count()
    return (6.0 if train else 2.0) * n * n_tokens
