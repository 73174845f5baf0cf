"""Lowered-HLO cost accounting: FLOPs, HBM traffic, collective bytes.

Parses `compiled.as_text()` (post-SPMD, so shapes are per-device) and
walks the computation call graph multiplying through `while` trip counts
— XLA's own cost_analysis counts a scanned body once; this parser counts
it `known_trip_count` times, which is what makes microbatched train
steps and decode loops come out right.

Accounting model:
  flops        — dot/convolution FLOPs (2 * out_elems * contraction).
  hbm_bytes    — operand + result bytes of every materializing op
                 (fusions count their boundary, not their interior).
  collectives  — payload bytes and *wire* bytes: payload scaled by the
                 ring factor of the collective kind (all-reduce moves
                 2(n-1)/n of its payload per link, all-gather /
                 reduce-scatter (n-1)/n, permutes 1.0).

`roofline_from_report` turns a CostReport into the three roofline time
terms under the reference chip below and names the dominant one.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

# Reference chip for roofline terms (a TPU-class accelerator).
PEAK_FLOPS = 197e12          # FLOP/s (bf16 systolic peak)
HBM_BANDWIDTH = 819e9        # B/s
ICI_BANDWIDTH = 45e9         # B/s per device, all links combined

# Sizes are in *bits* so sub-byte dtypes (s4/u4) stay integral: each
# array's bit volume is rounded up to whole bytes once, per array, the
# way a packed buffer is actually allocated.
_DTYPE_BITS = {
    "pred": 8, "s4": 4, "u4": 4, "s8": 8, "u8": 8,
    "s16": 16, "u16": 16, "f16": 16, "bf16": 16,
    "s32": 32, "u32": 32, "f32": 32,
    "s64": 64, "u64": 64, "f64": 64, "c64": 64, "c128": 128,
    "f8e4m3fn": 8, "f8e5m2": 8, "f8e4m3b11fnuz": 8, "f8e4m3fnuz": 8,
    "f8e5m2fnuz": 8,
    # zero-byte marker types (control-flow plumbing, not data)
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_TRIP_RE = re.compile(r'"known_trip_count"\s*:\s*\{\s*"n"\s*:\s*"(\d+)"')
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[([0-9,]+)\]<=")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_DIM_LABELS_RE = re.compile(r"dim_labels=([\w?]+)_([\w?]+)->([\w?]+)")

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                  "collective-permute", "collective-broadcast")

# ops that neither move HBM bytes nor compute (bookkeeping / control flow —
# control flow is descended into instead)
_FREE_OPS = frozenset({
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "while", "conditional", "call", "after-all", "partition-id",
    "replica-id", "add-dependency", "domain", "opt-barrier",
})


def _dims(dim_str: str) -> list[int]:
  return [int(d) for d in dim_str.split(",") if d]


def _shape_bytes(shape_str: str) -> int:
  """Total bytes of every array in a (possibly tuple) shape string.

  Always integral: bit volume is accumulated per array and rounded up to
  whole bytes per array (so `s4[5]` is 3 bytes, not 2.5)."""
  total = 0
  for dtype, dim_str in _SHAPE_RE.findall(shape_str):
    bits = _DTYPE_BITS.get(dtype)
    if bits is None:
      continue
    n = 1
    for d in _dims(dim_str):
      n *= d
    total += (n * bits + 7) // 8
  return total


def _first_array_dims(shape_str: str) -> Optional[list[int]]:
  m = _SHAPE_RE.search(shape_str)
  return _dims(m.group(2)) if m else None


def _wire_factor(kind: str, group_size: int) -> float:
  """Per-device wire bytes per payload byte on a ring of `group_size`."""
  if group_size <= 1:
    return 0.0
  n = float(group_size)
  if "all-reduce" in kind:
    return 2.0 * (n - 1.0) / n
  if "all-gather" in kind or "reduce-scatter" in kind:
    return (n - 1.0) / n
  return 1.0                       # all-to-all / permutes / broadcast


def _group_size(line: str, n_devices: int) -> int:
  m = _GROUPS_BRACE_RE.search(line)
  if m:
    return len(_dims(m.group(1)))
  m = _GROUPS_IOTA_RE.search(line)
  if m:
    dims = _dims(m.group(1))
    return dims[-1] if dims else n_devices
  return n_devices


# ---------------------------------------------------------------------------
# Report dataclasses.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CostReport:
  flops: float = 0.0
  dot_flops: float = 0.0
  hbm_bytes: float = 0.0
  collective_bytes: float = 0.0
  collective_wire_bytes: float = 0.0
  n_collectives: int = 0
  collective_by_kind: dict = dataclasses.field(default_factory=dict)
  hbm_by_shape: dict = dataclasses.field(default_factory=dict)
  #: {token: count} of things the parser could not fully account —
  #: "<unparsed>" for instruction lines _split_instr rejected (their
  #: bytes are still counted, as generic traffic from every shape token
  #: on the line) and "dtype:<name>" for dtypes missing from
  #: _DTYPE_BITS (whose arrays contribute zero bytes). Audit tooling
  #: (repro.analysis) surfaces this so parser gaps are visible instead
  #: of silently under-counting.
  unknown_ops: dict = dataclasses.field(default_factory=dict)

  def add(self, other: "CostReport", mult: float = 1.0) -> None:
    self.flops += other.flops * mult
    self.dot_flops += other.dot_flops * mult
    self.hbm_bytes += other.hbm_bytes * mult
    self.collective_bytes += other.collective_bytes * mult
    self.collective_wire_bytes += other.collective_wire_bytes * mult
    self.n_collectives += int(other.n_collectives * mult)
    for k, v in other.collective_by_kind.items():
      self.collective_by_kind[k] = (self.collective_by_kind.get(k, 0.0)
                                    + v * mult)
    for k, v in other.hbm_by_shape.items():
      self.hbm_by_shape[k] = self.hbm_by_shape.get(k, 0.0) + v * mult
    for k, v in other.unknown_ops.items():
      self.unknown_ops[k] = self.unknown_ops.get(k, 0) + int(v * mult)


@dataclasses.dataclass(frozen=True)
class Roofline:
  compute_s: float
  memory_s: float
  collective_s: float
  dominant: str                    # "compute" | "memory" | "collective"
  useful_flop_fraction: float
  roofline_fraction: float


def roofline_from_report(rep: CostReport,
                         model_flops: Optional[float] = None) -> Roofline:
  """The three roofline time terms under the reference chip.

  `model_flops` (the analytic 6ND/2ND estimate, per device) feeds
  useful_flop_fraction — how much of the executed FLOP volume is model
  math rather than remat/overhead."""
  compute_s = rep.flops / PEAK_FLOPS
  memory_s = rep.hbm_bytes / HBM_BANDWIDTH
  collective_s = rep.collective_wire_bytes / ICI_BANDWIDTH
  terms = {"compute": compute_s, "memory": memory_s,
           "collective": collective_s}
  dominant = max(terms, key=terms.get)
  total = compute_s + memory_s + collective_s
  useful = (model_flops / rep.flops if model_flops and rep.flops
            else (rep.dot_flops / rep.flops if rep.flops else 0.0))
  return Roofline(
      compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
      dominant=dominant,
      useful_flop_fraction=useful,
      roofline_fraction=terms[dominant] / total if total else 0.0)


# ---------------------------------------------------------------------------
# HLO text parsing.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Instr:
  opcode: str
  shape: str                       # result shape string
  operands: str                    # text inside the opcode's parens
  attrs: str                       # text after the closing paren
  line: str
  name: str = ""                   # instruction name, without the "%"


#: sentinel opcode for instruction lines `_split_instr` could not parse
_UNPARSED = "<unparsed>"

_HEADER_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_CALLED_RE = {
    "body": re.compile(r"body=%?([\w.\-]+)"),
    "condition": re.compile(r"condition=%?([\w.\-]+)"),
    "calls": re.compile(r"calls=%?([\w.\-]+)"),
    "to_apply": re.compile(r"to_apply=%?([\w.\-]+)"),
    "branches": re.compile(r"branch_computations=\{([^}]*)\}"),
    "true": re.compile(r"true_computation=%?([\w.\-]+)"),
    "false": re.compile(r"false_computation=%?([\w.\-]+)"),
}


_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")


def _split_instr(line: str) -> Optional[_Instr]:
  eq = line.find(" = ")
  if eq < 0:
    return None
  name = line[:eq].split()[-1].lstrip("%") if line[:eq].strip() else ""
  rest = line[eq + 3:]
  # result shape: either "(tuple, ...)" or "dtype[dims]{layout}"
  if rest.startswith("("):
    depth, i = 0, 0
    for i, ch in enumerate(rest):
      depth += ch == "("
      depth -= ch == ")"
      if depth == 0:
        break
    shape, rest = rest[:i + 1], rest[i + 1:].lstrip()
  else:
    sp = rest.find(" ")
    if sp < 0:
      return None
    shape, rest = rest[:sp], rest[sp + 1:]
  par = rest.find("(")
  if par < 0:
    return None
  opcode = rest[:par].strip()
  depth = 0
  end = len(rest) - 1
  for j in range(par, len(rest)):
    depth += rest[j] == "("
    depth -= rest[j] == ")"
    if depth == 0:
      end = j
      break
  return _Instr(opcode=opcode, shape=shape, operands=rest[par + 1:end],
                attrs=rest[end + 1:], line=line, name=name)


def _operand_shapes(ins: _Instr, shapes: dict) -> list[str]:
  """Result shape of each operand, looked up by name in its computation.

  Compiled HLO text names operands without their shapes
  (`dot(%a.1, %b.1)`), so operand shapes come from the instructions that
  define them; an operand defined nowhere in the computation adds none."""
  return [shapes[n] for n in _OPERAND_NAME_RE.findall(ins.operands)
          if n in shapes]


def _parse_computations(text: str) -> tuple[dict, Optional[str]]:
  comps: dict[str, list[_Instr]] = {}
  entry = None
  current: Optional[list] = None
  for line in text.splitlines():
    if current is None:
      m = _HEADER_RE.match(line)
      if m:
        name = m.group(2)
        comps[name] = current = []
        if m.group(1):
          entry = name
    elif line.strip() == "}":
      current = None
    else:
      ins = _split_instr(line)
      if ins is None and " = " in line:
        # An instruction line the splitter rejected. Keep it as a sentinel
        # so the cost walk can count its shape tokens as generic traffic
        # (and report it) instead of dropping it on the floor.
        ins = _Instr(opcode=_UNPARSED, shape=line, operands="", attrs="",
                     line=line)
      if ins is not None:
        current.append(ins)
  if entry is None and comps:
    entry = next(reversed(comps))
  return comps, entry


def _dot_flops(ins: _Instr, operands: list[str]) -> float:
  out = _first_array_dims(ins.shape) or []
  lhs = (_first_array_dims(operands[0]) if operands else None) or []
  m = _CONTRACT_RE.search(ins.attrs)
  contract = 1.0
  if m:
    for idx in _dims(m.group(1)):
      if idx < len(lhs):
        contract *= lhs[idx]
  out_elems = 1.0
  for d in out:
    out_elems *= d
  return 2.0 * out_elems * contract


def _conv_flops(ins: _Instr, operands: list[str]) -> float:
  out = _first_array_dims(ins.shape) or []
  if len(operands) < 2:
    return 0.0
  kernel = _first_array_dims(operands[1]) or []
  k_elems = 1.0
  for d in kernel:
    k_elems *= d
  m = _DIM_LABELS_RE.search(ins.attrs)
  o_dim = kernel[-1] if kernel else 1
  if m and kernel:
    o_idx = m.group(2).find("o")
    if 0 <= o_idx < len(kernel):
      o_dim = kernel[o_idx]
  out_elems = 1.0
  for d in out:
    out_elems *= d
  return 2.0 * out_elems * (k_elems / max(o_dim, 1))


def analyze_module(hlo_text: str, n_devices: int = 1) -> CostReport:
  """Parse a post-optimization HLO module dump into a CostReport.

  The module is already SPMD-partitioned, so all byte/FLOP figures are
  per-device; `n_devices` is the fallback collective group size when an
  instruction carries no parseable replica_groups."""
  comps, entry = _parse_computations(hlo_text)
  memo: dict[str, CostReport] = {}

  def called(ins: _Instr, key: str) -> Optional[str]:
    m = _CALLED_RE[key].search(ins.attrs)
    return m.group(1) if m else None

  def cost(name: str) -> CostReport:
    if name in memo:
      return memo[name]
    memo[name] = CostReport()      # cycle guard (HLO graphs are acyclic)
    rep = CostReport()
    instrs = comps.get(name, ())
    shapes = {ins.name: ins.shape for ins in instrs if ins.name}
    for ins in instrs:
      op = ins.opcode
      for d, _ in _SHAPE_RE.findall(ins.shape):
        if d not in _DTYPE_BITS:
          key = f"dtype:{d}"
          rep.unknown_ops[key] = rep.unknown_ops.get(key, 0) + 1
      if op == _UNPARSED:
        rep.unknown_ops[_UNPARSED] = rep.unknown_ops.get(_UNPARSED, 0) + 1
        rep.hbm_bytes += _shape_bytes(ins.line)
        continue
      if op == "while":
        m = _TRIP_RE.search(ins.attrs)
        trip = float(m.group(1)) if m else 1.0
        body = called(ins, "body")
        cond = called(ins, "condition")
        if body:
          rep.add(cost(body), trip)
        if cond:
          rep.add(cost(cond), trip)
        continue
      if op == "conditional":
        branches = []
        m = _CALLED_RE["branches"].search(ins.attrs)
        if m:
          branches = [b.strip().lstrip("%") for b in m.group(1).split(",")]
        else:
          branches = [b for b in (called(ins, "true"), called(ins, "false"))
                      if b]
        if branches:
          costs = [cost(b) for b in branches if b in comps]
          if costs:
            rep.add(max(costs, key=lambda c: c.flops + c.hbm_bytes))
        continue
      if op == "call":
        tgt = called(ins, "to_apply")
        if tgt:
          rep.add(cost(tgt))
        continue
      if op == "fusion":
        tgt = called(ins, "calls")
        if tgt:
          inner = cost(tgt)
          rep.flops += inner.flops          # dots fused into the kernel
          rep.dot_flops += inner.dot_flops
        # fall through: the fusion boundary is the HBM traffic
      operands = _operand_shapes(ins, shapes)
      operand_bytes = sum(_shape_bytes(o) for o in operands)
      if op == "dot":
        f = _dot_flops(ins, operands)
        rep.flops += f
        rep.dot_flops += f
      elif op == "convolution":
        rep.flops += _conv_flops(ins, operands)
      base = op.replace("-start", "")
      if base in COLLECTIVE_OPS and not op.endswith("-done"):
        payload = max(_shape_bytes(ins.shape), operand_bytes)
        g = _group_size(ins.line, n_devices)
        wire = payload * _wire_factor(base, g)
        rep.collective_bytes += payload
        rep.collective_wire_bytes += wire
        rep.n_collectives += 1
        rep.collective_by_kind[base] = (
            rep.collective_by_kind.get(base, 0.0) + wire)
      if op in _FREE_OPS or op.endswith("-done"):
        continue
      b = _shape_bytes(ins.shape) + operand_bytes
      rep.hbm_bytes += b
      out_b = _shape_bytes(ins.shape)
      if out_b:
        rep.hbm_by_shape[ins.shape] = (
            rep.hbm_by_shape.get(ins.shape, 0.0) + out_b)
    memo[name] = rep
    return rep

  if entry is None:
    return CostReport()
  return cost(entry)
