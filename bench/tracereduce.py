"""Reduce a JAX profiler trace (`.xplane.pb`) to the benchmark's numbers.

Reads with `jax.profiler.ProfileData` only. What it takes from a trace:

  device planes   planes named "/device:<KIND>:<n>" (TPU, GPU); on each,
                  the "XLA Ops" line holds one event per device operation
                  and the "XLA Modules" line one per program execution
  host spans      events whose name starts with "bench." (the
                  benchmark's own `TraceAnnotation`s) on host lines

and what it gives: the traced window (the harness's "bench.window" span),
device busy time (the union of op intervals clipped to the window,
averaged over the chips), the same inside the host's spans of one name,
program executions by module, device self time by operation (an op's
time less that of the ops nested in it, such as a loop's body), the
longest idle gaps with the host span they fell in, and each call of a
Pallas kernel with the copies that staged its operands.

On a TPU an op event's name is its HLO instruction ("%gru_cell.5 =
bf16[16,1280]{...} custom-call(bf16[16,3,1280]{...} %x, ...)"): a Pallas
kernel's instruction takes the kernel's name, and `hlo_shapes` reads the
result and operand shapes from it. Host and device clocks agree to a
fraction of a millisecond, so an op or a program counts in the window
when its midpoint lies inside.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import glob
import math
import os
import re

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."         # the harness's own TraceAnnotations
TOP = 10                       # entries of each breakdown list
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
  name: str
  start: int                    # ns
  end: int                      # ns
  self_ns: int = 0              # duration less nested events' durations

  @property
  def op(self) -> str:
    """The HLO instruction name ("%gru_cell.5"), or the event name."""
    return self.name.split(" = ", 1)[0]


@dataclasses.dataclass
class Summary:
  window: tuple                 # (start_ns, end_ns)
  devices: int
  busy_s: float                 # per chip, averaged
  window_s: float
  op_seconds: dict              # op name -> device self seconds (all chips)
  op_counts: dict               # op name -> events
  module_counts: dict           # program name -> executions (all chips)
  idle_gaps: list               # [(host span, seconds)], longest first
  ops: list                     # every device op Event inside the window
  plane_ops: dict = dataclasses.field(default_factory=dict)
  plane_modules: dict = dataclasses.field(default_factory=dict)
  host_spans: list = dataclasses.field(default_factory=list)

  @property
  def launches(self) -> int:
    return sum(self.module_counts.values())

  def breakdown(self) -> dict:
    ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:TOP]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in self.idle_gaps[:TOP]]}


def _events(line) -> list:
  out = []
  for e in line.events:
    start = int(e.start_ns)
    out.append(Event(e.name, start, start + int(e.duration_ns)))
  return out


def _nest(events: list) -> None:
  """Set each event's self time: its duration less its children's."""
  stack = []
  for e in sorted(events, key=lambda e: (e.start, -e.end)):
    e.self_ns = e.end - e.start
    while stack and stack[-1].end <= e.start:
      stack.pop()
    if stack and e.end <= stack[-1].end:
      stack[-1].self_ns -= e.end - e.start
    stack.append(e)


def _union(intervals: list) -> list:
  merged = []
  for s, e in sorted(intervals):
    if merged and s <= merged[-1][1]:
      merged[-1][1] = max(merged[-1][1], e)
    else:
      merged.append([s, e])
  return merged


def reduce_profile(profile, window_span: str = WINDOW_SPAN) -> Summary:
  device_ops, device_modules, host = {}, {}, []
  for plane in profile.planes:
    if plane.name.startswith("/device:") and "CPU" not in plane.name:
      for line in plane.lines:
        if line.name == OPS_LINE:
          device_ops.setdefault(plane.name, []).extend(_events(line))
        elif line.name == MODULES_LINE:
          device_modules.setdefault(plane.name, []).extend(_events(line))
    elif plane.name.startswith("/host:"):
      for line in plane.lines:
        host.extend(e for e in _events(line)
                    if e.name.startswith(SPAN_PREFIX))
  windows = [e for e in host if e.name == window_span]
  if not windows:
    raise ValueError(f"no {window_span!r} span in the trace")
  w0, w1 = windows[0].start, windows[0].end
  if not device_ops:
    raise ValueError("no device operations in the trace")

  def inside(e: Event) -> bool:
    return w0 <= (e.start + e.end) // 2 < w1

  busy, ops, plane_ops = 0, [], {}
  gaps = []
  for name, evs in device_ops.items():
    _nest(evs)
    evs = [e for e in evs if inside(e)]
    ops.extend(evs)
    plane_ops[name] = sorted(evs, key=lambda e: e.start)
    merged = _union([(max(e.start, w0), min(e.end, w1)) for e in evs])
    busy += sum(e - s for s, e in merged)
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    for s, e in zip(edges[::2], edges[1::2]):
      if e > s:
        gaps.append((s, e))
  op_s, op_n = collections.Counter(), collections.Counter()
  for e in ops:
    op_s[e.op] += e.self_ns * 1e-9
    op_n[e.op] += 1
  modules = collections.Counter()
  for evs in device_modules.values():
    for e in evs:
      if inside(e):
        modules[e.name] += 1
  spans = sorted((e for e in host if e.name != window_span),
                 key=lambda e: e.end - e.start)

  def label(s: int, e: int) -> str:
    mid = (s + e) // 2
    for sp in spans:                      # innermost (shortest) first
      if sp.start <= mid < sp.end:
        return sp.name
    return "outside any bench span"

  gaps.sort(key=lambda g: g[0] - g[1])
  idle = [(label(s, e), (e - s) * 1e-9) for s, e in gaps[:TOP]]
  n = len(device_ops)
  return Summary(window=(w0, w1), devices=n, busy_s=busy * 1e-9 / n,
                 window_s=(w1 - w0) * 1e-9, op_seconds=dict(op_s),
                 op_counts=dict(op_n), module_counts=dict(modules),
                 idle_gaps=idle, ops=ops, plane_ops=plane_ops,
                 plane_modules={k: sorted((e for e in v if inside(e)),
                                          key=lambda e: e.start)
                                for k, v in device_modules.items()},
                 host_spans=[e for e in spans if inside(e)])


def busy_within(summary: Summary, span: str) -> tuple:
  """(device busy seconds, span seconds) inside the union of the host
  spans named `span`, both averaged over the chips; (0, 0) without one."""
  spans = _union([(e.start, e.end) for e in summary.host_spans
                  if e.name == span])
  if not spans or not summary.plane_ops:
    return 0.0, 0.0
  busy = 0
  for evs in summary.plane_ops.values():
    ops = _union([(e.start, e.end) for e in evs])
    i = 0
    for s0, s1 in spans:
      while i < len(ops) and ops[i][1] <= s0:
        i += 1
      j = i
      while j < len(ops) and ops[j][0] < s1:
        busy += min(ops[j][1], s1) - max(ops[j][0], s0)
        j += 1
  total = sum(e - s for s, e in spans)
  return busy * 1e-9 / len(summary.plane_ops), total * 1e-9


def find_xplane(trace_dir: str) -> str:
  files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                    recursive=True)
  if len(files) != 1:
    raise ValueError(f"expected one .xplane.pb under {trace_dir}, found "
                     f"{files}")
  return files[0]


def reduce_file(path: str, **kw) -> Summary:
  import jax
  return reduce_profile(jax.profiler.ProfileData.from_file(path), **kw)


def reduce_dir(trace_dir: str, **kw) -> Summary:
  return reduce_file(find_xplane(trace_dir), **kw)


_SHAPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\](\{[^}]*\})?")
_TYPED = re.compile(_SHAPE.pattern + r"\s+%([\w.\-]+)")  # shape, operand
_SPACE = re.compile(r"S\((\d+)\)")


def _parse(dt: str, dims: str, layout: str) -> tuple:
  space = _SPACE.search(layout or "")
  return (dt, tuple(int(d) for d in dims.split(",") if d),
          int(space.group(1)) if space else 0)


def _shapes(text: str) -> list:
  return [_parse(*m) for m in _SHAPE.findall(text)]


def hlo_shapes(name: str) -> tuple:
  """(result, [operands]) of an HLO instruction's text, each a
  (dtype, dims, memory space) triple: space 0 is HBM, a layout's "S(n)"
  marks another (on TPU, on-chip memory that XLA staged the value in).
  Operands are the shapes followed by an instruction name ("%x"), which
  leaves out shapes repeated in attributes. A tuple result gives None."""
  _, _, rest = name.partition(" = ")
  out = _SHAPE.match(rest)
  return (_parse(*out.groups()) if out else None,
          [_parse(*m.groups()[:3]) for m in _TYPED.finditer(rest)])


_OPCODE = re.compile(r"\s([a-z][a-z0-9_\-]*)\(")
_NAME = re.compile(r"%([\w.\-]+)")
# instructions that only move or re-lay data on its way to a kernel:
# copies, their asynchronous start/done pairs (slices of a weight are
# async-start/async-done), bitcasts and XLA's "ConcatBitcast" custom call
_STAGING = ("copy", "copy-start", "copy-done", "async-start", "async-done",
            "bitcast")
_READS_HBM = ("copy", "copy-start", "async-start")


@functools.lru_cache(maxsize=None)
def hlo_parts(name: str) -> tuple:
  """(result text, opcode, operand text) of an HLO instruction's text."""
  _, _, rest = name.partition(" = ")
  m = _OPCODE.search(rest)
  if not m:
    return rest, "", ""
  depth, i = 0, m.end() - 1
  for i in range(m.end() - 1, len(rest)):
    depth += {"(": 1, ")": -1}.get(rest[i], 0)
    if depth == 0:
      break
  return rest[:m.start()], m.group(1), rest[m.end():i]


@functools.lru_cache(maxsize=None)
def _is_staging(name: str) -> bool:
  _, opcode, _ = hlo_parts(name)
  if opcode == "custom-call":
    return "tpu_custom_call" not in name
  return opcode in _STAGING


@functools.lru_cache(maxsize=None)
def _staged_from_hbm(name: str) -> int:
  """Bytes that a copy or an async start moves from HBM into another
  memory space: its first result held outside HBM, where an operand is
  in HBM; else 0."""
  result, opcode, args = hlo_parts(name)
  if opcode not in _READS_HBM or not any(
      sp == 0 for _, _, sp in _shapes(args)):
    return 0
  from bench.kernels.roofline import shape_bytes
  for shape in _shapes(result):
    if shape[2] == 1:
      return shape_bytes(shape)
  return 0


@dataclasses.dataclass
class KernelCall:
  event: Event                  # the kernel's own op event
  start: int                    # ns: the earliest staging of an operand
  staged_bytes: int             # bytes its staging copies read from HBM


def kernel_calls(summary: Summary, kernel: str) -> list:
  """Each call of `kernel` with the staging that fed it.

  XLA may stage a kernel's operands in on-chip memory before the call
  (an "S(1)" layout): a weight is sliced in by asynchronous copies that
  run behind earlier ops, or copied and re-laid out by copies that hold
  the core. Following the kernel's operands back through such
  instructions in the same program execution (a bitcast, which leaves no
  event, resolves to the latest staged value of the same type and size),
  the call runs from the start of its earliest staging to its end, and
  the bytes those copies read from HBM are its own. Every byte counted
  moves inside that span, so its least time cannot exceed it."""
  out = []
  pat = re.compile(r"%" + re.escape(kernel) + r"(\.\d+)?$")   # %kernel[.n]
  for plane, evs in summary.plane_ops.items():
    starts = [e.start for e in evs]
    mods = summary.plane_modules.get(plane, [])
    mod_starts = [m.start for m in mods]
    for at, k in enumerate(evs):
      if not pat.match(k.op):
        continue
      i = bisect.bisect_right(mod_starts, k.start) - 1
      lo = mods[i].start if i >= 0 and k.start < mods[i].end else k.start
      prior = [e for e in evs[bisect.bisect_left(starts, lo):at]
               if e.end <= k.start]
      start, staged, seen = k.start, 0, set()
      todo = [(n, sh, k.start) for n, sh in _operands(k.name)]
      while todo:
        name, shape, before = todo.pop()
        src = _resolve(prior, name, shape, before)
        if src is None or id(src) in seen or not _is_staging(src.name):
          continue
        seen.add(id(src))
        start = min(start, src.start)
        staged += _staged_from_hbm(src.name)
        todo.extend((n, sh, src.start) for n, sh in _operands(src.name))
      out.append(KernelCall(k, start, staged))
  return out


@functools.lru_cache(maxsize=None)
def _operands(name: str) -> list:
  """[(operand instruction name, its shape or None for a tuple)]."""
  _, _, args = hlo_parts(name)
  typed = {"%" + m.group(4): _parse(*m.groups()[:3])
           for m in _TYPED.finditer(args)}
  return tuple(("%" + n, typed.get("%" + n)) for n in _NAME.findall(args))


def _resolve(prior: list, name: str, shape, before: int):
  """The latest event before `before` that computed `name`; a bitcast,
  which has no event, resolves to the latest staging event whose first
  result has the bitcast's type, size and memory space."""
  for e in reversed(prior):
    if e.end <= before and e.op == name:
      return e
  if not name.startswith("%bitcast") or shape is None:
    return None
  want = (shape[0], math.prod(shape[1]), shape[2])
  for e in reversed(prior):
    if e.end > before or not _is_staging(e.name):
      continue
    res = _shapes(hlo_parts(e.name)[0])
    if res and (res[0][0], math.prod(res[0][1]), res[0][2]) == want:
      return e
  return None
