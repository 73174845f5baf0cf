"""Recursive jaxpr walking: name-stack resolution + operand provenance.

The two jaxpr-level checks both need the same walk:

* **dispatch coverage** needs, for every `dot_general`, (a) the full
  name stack — including the `dispatch:{regime}:c{id}` scope
  `kernels.dispatch.gemm` wraps routed GEMMs in — and (b) whether either
  operand is derived from a *parameter* leaf. A dot with a param operand
  and no dispatch scope is a GEMM that bypassed the dispatcher.

* **quantization integrity** needs to know when a value derived from an
  int8 parameter leaf is `convert_element_type`'d to a floating dtype —
  a dequantize, the exact op PTQ exists to eliminate. Integer widening
  (int8 -> int32 accumulation inside the w8a8 oracle) is legitimate and
  tracked through.

Provenance is propagated conservatively, through *unary* structural ops
only (TRANSPARENT below): a bias-add or norm-scale involving a param
does NOT taint its activation output, so attention's activation x cache
contractions stay clean. Sub-jaxprs (scan/pjit/cond/while/custom_*) are
descended with their invars mapped to the enclosing equation's operands;
`pallas_call` is deliberately NOT descended — the kernel body belongs to
the dispatch scope its call site carries.

Name stacks inside a sub-jaxpr usually already carry the enclosing
scopes (same-trace lowering), but a *cached* inner jaxpr (a module-level
jit hit from an earlier trace) keeps its stale stacks. The walk
therefore threads the enclosing equation's resolved stack down as a
prefix, and joins it only when the inner stack does not already contain
it — so a dot inside a reused pjit still resolves to the CURRENT
dispatch scope first. Correlation parsers must accordingly take the
FIRST dispatch scope in a stack, never the last.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

import jax.numpy as jnp
from jax.extend import core

#: unary structural ops provenance flows through (first operand only)
TRANSPARENT = frozenset({
    "convert_element_type", "reshape", "transpose", "broadcast_in_dim",
    "squeeze", "expand_dims", "slice", "dynamic_slice", "rev", "copy",
    "reduce_precision", "stop_gradient",
})

#: primitives that imply a host round-trip / transfer inside the program
HOST_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "infeed", "outfeed", "device_put", "copy_to_host_async",
})

DOT_PRIMS = frozenset({"dot_general"})

#: first dispatch correlation scope in a name stack (see module docstring)
DISPATCH_SCOPE_RE = re.compile(r"dispatch:([a-z0-9_]+):c(\d+)")

_NOFLAG = (False, False)         # (param_derived, int8_param_derived)


@dataclasses.dataclass(frozen=True)
class DotSite:
  """One dot_general: where it is and what feeds it."""
  name_stack: str
  shapes: tuple                  # ((lhs...), (rhs...))
  param_operands: tuple          # (lhs_from_param, rhs_from_param)

  def dispatch_scope(self) -> Optional[tuple]:
    """(regime, call_id) of the first dispatch scope, or None."""
    m = DISPATCH_SCOPE_RE.search(self.name_stack)
    return (m.group(1), int(m.group(2))) if m else None


@dataclasses.dataclass(frozen=True)
class ConvertSite:
  """An int8-param-derived value converted to a floating dtype."""
  name_stack: str
  shape: tuple
  dst_dtype: str


@dataclasses.dataclass(frozen=True)
class PrimSite:
  """A host/transfer primitive occurrence."""
  name_stack: str
  prim: str


@dataclasses.dataclass
class WalkResult:
  dots: list = dataclasses.field(default_factory=list)
  int8_converts: list = dataclasses.field(default_factory=list)
  host_prims: list = dataclasses.field(default_factory=list)
  n_eqns: int = 0


def _as_jaxpr(x):
  return x.jaxpr if isinstance(x, core.ClosedJaxpr) else x


def _sub_jaxprs(eqn) -> list:
  """[(inner Jaxpr, operand list aligned with its invars)] for one eqn.

  A None operand means "untracked" (conservative: inner values derived
  from it carry no provenance)."""
  prim = eqn.primitive.name
  if prim == "pallas_call":
    return []
  if prim == "while":
    cn = eqn.params["cond_nconsts"]
    bn = eqn.params["body_nconsts"]
    carry = list(eqn.invars[cn + bn:])
    return [
        (_as_jaxpr(eqn.params["cond_jaxpr"]),
         list(eqn.invars[:cn]) + carry),
        (_as_jaxpr(eqn.params["body_jaxpr"]),
         list(eqn.invars[cn:cn + bn]) + carry),
    ]
  if prim == "cond":
    ops = list(eqn.invars[1:])
    return [(_as_jaxpr(b), ops) for b in eqn.params.get("branches", ())]
  out = []
  for val in eqn.params.values():
    for v in (val if isinstance(val, (tuple, list)) else (val,)):
      if isinstance(v, (core.ClosedJaxpr, core.Jaxpr)):
        j = _as_jaxpr(v)
        if len(j.invars) == len(eqn.invars):
          # pjit / scan / remat / custom_* all align invars positionally
          out.append((j, list(eqn.invars)))
        else:
          out.append((j, [None] * len(j.invars)))
  return out


def walk(closed: core.ClosedJaxpr, n_params: int,
         int8_param_idx: frozenset = frozenset()) -> WalkResult:
  """Walk `closed` (and every reachable sub-jaxpr), tracking provenance
  from the first `n_params` flattened invars (the params argument) and,
  within those, the `int8_param_idx` positions (int8 weight leaves)."""
  res = WalkResult()

  def visit(jaxpr: core.Jaxpr, in_flags, prefix: str) -> None:
    env = {}
    for v, fl in zip(jaxpr.invars, in_flags):
      if fl != _NOFLAG and not isinstance(v, core.Literal):
        env[v] = fl

    def flag(atom):
      if isinstance(atom, core.Literal):
        return _NOFLAG
      return env.get(atom, _NOFLAG)

    for eqn in jaxpr.eqns:
      res.n_eqns += 1
      prim = eqn.primitive.name
      ns = str(eqn.source_info.name_stack)
      if prefix and prefix not in ns:
        full = f"{prefix}/{ns}" if ns else prefix
      else:
        full = ns
      if prim in DOT_PRIMS:
        ops = eqn.invars[:2]
        res.dots.append(DotSite(
            name_stack=full,
            shapes=tuple(tuple(getattr(a.aval, "shape", ()))
                         for a in ops),
            param_operands=tuple(flag(a)[0] for a in ops)))
      elif prim in HOST_PRIMS:
        res.host_prims.append(PrimSite(name_stack=full, prim=prim))
      if prim == "convert_element_type":
        src = flag(eqn.invars[0])
        if src != _NOFLAG:
          dst = eqn.params.get("new_dtype")
          if dst is not None and jnp.issubdtype(dst, jnp.floating) \
              and src[1]:
            res.int8_converts.append(ConvertSite(
                name_stack=full,
                shape=tuple(eqn.invars[0].aval.shape),
                dst_dtype=str(jnp.dtype(dst))))
            src = (src[0], False)    # dequantized: no longer int8-derived
          env[eqn.outvars[0]] = src
      elif prim in TRANSPARENT:
        src = flag(eqn.invars[0]) if eqn.invars else _NOFLAG
        if src != _NOFLAG and len(eqn.outvars) == 1:
          env[eqn.outvars[0]] = src
      for sub, operands in _sub_jaxprs(eqn):
        sub_flags = [_NOFLAG if a is None else flag(a) for a in operands]
        visit(sub, sub_flags, full)

  in_flags = [(i < n_params, i in int8_param_idx)
              for i in range(len(closed.jaxpr.invars))]
  visit(closed.jaxpr, in_flags, "")
  return res


def check_param_alignment(closed: core.ClosedJaxpr, flat_params) -> None:
  """Assert the first len(flat_params) invars ARE the params leaves (the
  positional assumption `walk` rests on). Raises on drift."""
  invars = closed.jaxpr.invars
  if len(invars) < len(flat_params):
    raise AssertionError(
        f"jaxpr has {len(invars)} invars < {len(flat_params)} param leaves")
  for i, leaf in enumerate(flat_params):
    aval = invars[i].aval
    if tuple(aval.shape) != tuple(leaf.shape):
      raise AssertionError(
          f"invar {i} shape {tuple(aval.shape)} != param leaf shape "
          f"{tuple(leaf.shape)}: params are not the leading invars")
