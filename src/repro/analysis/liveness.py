"""Static peak live-buffer estimate from a jaxpr: last-use liveness.

The embedded-deployment number the paper cares about is not "how many
bytes do the weights occupy" (the compression ledger answers that) but
"how many bytes must be resident to take one decode step". This pass
computes a static estimate straight from the traced jaxpr, no
execution:

* every program input (params + state + tokens) is resident for the
  whole program — `input_bytes`;
* equation outputs are allocated in program order and freed after their
  last use (jaxpr outvars are never freed — they outlive the program);
* control-flow bodies (scan/while/pjit/cond/remat, anything
  `jaxpr_walk._sub_jaxprs` yields) contribute their own transient peak
  *on top of* the buffers live at their call site — one iteration's
  worth, since carries reuse buffers across iterations while stacked
  scan outputs are allocated by the outer equation's outvars;
* donated state leaves are credited: an output that aliases a donated
  input (greedy shape+dtype match, the same contract
  `checks._donation_findings` verifies against the lowered StableHLO)
  writes into the input's buffer and allocates nothing.

The result is an *estimate* — XLA's buffer assignment can fuse away
intermediates we count and materialize copies we don't — but it is
deterministic, cheap, and moves with the program structure, which is
exactly what a budget gate needs.
"""
from __future__ import annotations

import dataclasses

from jax.core import DropVar
from jax.extend import core

from repro.analysis.jaxpr_walk import _sub_jaxprs


def _aval_bytes(aval) -> int:
  """Whole-byte size of one abstract value (int4 packs 2/byte)."""
  shape = getattr(aval, "shape", None)
  dtype = getattr(aval, "dtype", None)
  if shape is None or dtype is None:
    return 0
  n = 1
  for d in shape:
    n *= int(d)
  itembits = dtype.itemsize * 8
  if "int4" in dtype.name:
    itembits = 4
  return (n * itembits + 7) // 8


def _var_bytes(v) -> int:
  return _aval_bytes(getattr(v, "aval", None))


def _transient_peak(jaxpr: core.Jaxpr, credited: frozenset) -> int:
  """Peak bytes of eqn-allocated buffers, relative to the frame's inputs.

  Frame invars/constvars are the caller's problem (already resident
  there); `credited` vars allocate zero bytes (donation aliasing)."""
  never_free = set()
  for v in jaxpr.outvars:
    if isinstance(v, core.Var):
      never_free.add(v)
  last_use: dict = {}
  for i, eqn in enumerate(jaxpr.eqns):
    for a in eqn.invars:
      if isinstance(a, core.Var):
        last_use[a] = i
  frees_at: list = [[] for _ in jaxpr.eqns]
  for v, i in last_use.items():
    if v not in never_free:
      frees_at[i].append(v)

  live = 0
  peak = 0
  owned: dict = {}                 # var -> bytes this frame allocated
  for i, eqn in enumerate(jaxpr.eqns):
    inner = 0
    for sub, _ in _sub_jaxprs(eqn):
      inner = max(inner, _transient_peak(sub, credited))
    peak = max(peak, live + inner)
    for v in eqn.outvars:
      if isinstance(v, DropVar):
        continue
      b = 0 if v in credited else _var_bytes(v)
      owned[v] = b
      live += b
    peak = max(peak, live)
    for v in frees_at[i]:
      if v in owned:
        live -= owned.pop(v)
    # outputs never read again (and not program outputs) die immediately
    for v in eqn.outvars:
      if (v in owned and v not in last_use and v not in never_free
          and not isinstance(v, DropVar)):
        live -= owned.pop(v)
  return peak


@dataclasses.dataclass(frozen=True)
class LivenessReport:
  input_bytes: int                 # all program inputs, resident throughout
  donated_bytes: int               # inputs whose buffers outputs may reuse
  credited_bytes: int              # output bytes matched to donated inputs
  output_bytes: int                # program outputs (state', logits, ...)
  transient_bytes: int             # peak eqn-allocated bytes (post credit)
  peak_bytes: int                  # input_bytes + transient_bytes


def analyze_jaxpr(closed: core.ClosedJaxpr, *, n_params: int = 0,
                  n_donated: int = 0) -> LivenessReport:
  """Liveness for one traced program.

  `n_params`/`n_donated` follow the TraceTarget invar layout: flattened
  invars are params (n_params), then the donated state tree (n_donated),
  then the remaining inputs."""
  jaxpr = closed.jaxpr
  input_bytes = sum(_var_bytes(v) for v in jaxpr.invars)
  input_bytes += sum(_var_bytes(v) for v in jaxpr.constvars)

  donated = list(jaxpr.invars[n_params:n_params + n_donated])
  donated_bytes = sum(_var_bytes(v) for v in donated)

  # greedy donation credit: each donated input buffer can absorb one
  # output of identical shape+dtype
  pool: dict = {}
  for v in donated:
    aval = getattr(v, "aval", None)
    key = (tuple(getattr(aval, "shape", ())), str(getattr(aval, "dtype", "")))
    pool[key] = pool.get(key, 0) + 1
  credited = set()
  credited_bytes = 0
  for v in jaxpr.outvars:
    if not isinstance(v, core.Var) or v in credited:
      continue
    aval = getattr(v, "aval", None)
    key = (tuple(getattr(aval, "shape", ())), str(getattr(aval, "dtype", "")))
    if pool.get(key, 0) > 0:
      pool[key] -= 1
      credited.add(v)
      credited_bytes += _var_bytes(v)

  output_bytes = sum(_var_bytes(v) for v in jaxpr.outvars
                     if isinstance(v, core.Var))
  transient = _transient_peak(jaxpr, frozenset(credited))
  return LivenessReport(
      input_bytes=input_bytes,
      donated_bytes=donated_bytes,
      credited_bytes=credited_bytes,
      output_bytes=output_bytes,
      transient_bytes=transient,
      peak_bytes=input_bytes + transient)
