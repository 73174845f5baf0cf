"""Truncated-SVD warmstarting (paper §3, stage 1 -> stage 2).

Implements:
  * the Lemma-1 balanced split  W = (U sqrt(S)) (sqrt(S) V^T), which attains
    equality in the variational characterization — used to factorize a
    pretrained unfactored model into the stage-1 form;
  * explained-variance rank truncation ("retain only as many singular values
    as required to explain a specified percentage of the variance",
    Prabhavalkar et al. 2016);
  * tree-level warmstart: stage-1 (full-rank factored, trace-norm-trained)
    -> stage-2 (rank-truncated factored) models.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.factored import FactoredLinear, map_factored_leaves


def _host(x: jax.Array) -> jax.Array:
  """x on the host CPU device. The warmstart SVDs run there: they run once
  per leaf, and an accelerator compiles a separate SVD program for every
  leaf shape (minutes per model on a TPU)."""
  return jax.device_put(x, jax.devices("cpu")[0])


def _to_default_device(x: jax.Array) -> jax.Array:
  return jnp.asarray(np.asarray(x))


def balanced_split(w: jax.Array, rank: Optional[int] = None
                   ) -> tuple[jax.Array, jax.Array]:
  """Factor w (m, n) into u (m, r), v (r, n) with u = U sqrt(S), v = sqrt(S)V^T.

  This choice attains equality in Lemma 1: ||u||_F^2 = ||v||_F^2 = ||w||_T
  (when rank is full), so a stage-1 model warmstarted this way starts *at*
  the variational minimum of the penalty.
  """
  if w.ndim != 2:
    raise ValueError(f"balanced_split expects 2D, got {w.shape}")
  r = min(w.shape) if rank is None else rank
  uu, s, vt = jnp.linalg.svd(_host(w).astype(jnp.float32),
                             full_matrices=False)
  sq = jnp.sqrt(s[:r])
  u = (uu[:, :r] * sq[None, :]).astype(w.dtype)
  v = (sq[:, None] * vt[:r, :]).astype(w.dtype)
  return _to_default_device(u), _to_default_device(v)


def explained_variance_rank(s: jax.Array | np.ndarray, threshold: float) -> int:
  """Smallest r with sum_{i<r} s_i^2 >= threshold * sum s_i^2 (concrete int)."""
  s = np.asarray(s, dtype=np.float64)
  var = s * s
  cum = np.cumsum(var)
  total = cum[-1]
  if total <= 0:
    return 1
  return int(np.searchsorted(cum / total, threshold) + 1)


@dataclasses.dataclass(frozen=True)
class TruncationSpec:
  """How to pick the stage-2 rank for each GEMM."""
  variance_threshold: Optional[float] = 0.9   # paper's knob (Fig. 3/4)
  fixed_rank: Optional[int] = None            # override: exact rank
  max_rank: Optional[int] = None              # cap (latency budget)
  round_to: int = 8                           # TPU-friendly rank rounding

  def pick(self, s: np.ndarray) -> int:
    if self.fixed_rank is not None:
      r = self.fixed_rank
    else:
      r = explained_variance_rank(s, self.variance_threshold)
    if self.max_rank is not None:
      r = min(r, self.max_rank)
    r = max(self.round_to, int(np.ceil(r / self.round_to)) * self.round_to)
    return min(r, len(s))


def _whitener(cov: np.ndarray, eps: float = 1e-6) -> np.ndarray:
  """Cholesky factor L of a symmetrized, trace-regularized Gram matrix.

  cov is E[x x^T] (m, m) from the calibration tap; the regularization
  keeps the factorization defined when calibration saw fewer rows than
  m (rank-deficient Gram) without perturbing well-conditioned stats."""
  m = cov.shape[0]
  c = np.asarray(cov, np.float64)
  c = 0.5 * (c + c.T)
  c = c + (eps * np.trace(c) / m + 1e-12) * np.eye(m)
  return np.linalg.cholesky(c)


def activation_split(w, cov: np.ndarray, spec: TruncationSpec
                     ) -> tuple[jax.Array, jax.Array, np.ndarray]:
  """Activation-weighted truncated split of one 2-D GEMM (LiteASR).

  Spectrum-only truncation minimizes ||W - UV||_F, which weights every
  input direction equally; what serving accuracy cares about is the
  OUTPUT error E||x W - x UV||^2 = ||L^T (W - UV)||_F^2 with L the
  Cholesky factor of E[x x^T]. The minimizer is the truncated SVD of
  the whitened matrix L^T W = U' S V'^T mapped back through L^{-T}:

      u = L^{-T} U'_r sqrt(S_r),   v = sqrt(S_r) V'_r^T

  and the *rank itself* is picked from the whitened spectrum S — ranks
  follow output-reconstruction energy, not weight energy. Returns
  (u, v, whitened_singular_values)."""
  wl = np.asarray(w, np.float64)
  lch = _whitener(cov)
  uu, s, vt = np.linalg.svd(lch.T @ wl, full_matrices=False)
  r = spec.pick(s)
  sq = np.sqrt(s[:r])
  u = np.linalg.solve(lch.T, uu[:, :r] * sq[None, :])
  v = sq[:, None] * vt[:r, :]
  return (jnp.asarray(u.astype(np.asarray(w).dtype)),
          jnp.asarray(v.astype(np.asarray(w).dtype)), s)


def truncate_leaf(leaf: FactoredLinear, spec: TruncationSpec,
                  cov: Optional[np.ndarray] = None) -> FactoredLinear:
  """Stage-2 warmstart for one GEMM: truncated balanced SVD of product().

  With `cov` (the calibrated input Gram matrix E[x x^T]: (m, m), or
  (L, m, m) per-layer for a stacked leaf, or (m, m) broadcast over the
  stack) the split is activation-weighted: rank and factors both come
  from the whitened spectrum (see `activation_split`)."""
  w = leaf.product()
  if w.ndim == 2:
    if cov is not None:
      u, v, _ = activation_split(w, np.asarray(cov), spec)
      return FactoredLinear(w=None, u=u, v=v, name=leaf.name,
                            group=leaf.group)
    s = np.asarray(jnp.linalg.svd(_host(w).astype(jnp.float32),
                                  compute_uv=False))
    r = spec.pick(s)
    u, v = balanced_split(w, r)
    return FactoredLinear(w=None, u=u, v=v, name=leaf.name, group=leaf.group)
  # Stacked (L, m, n): pick one rank for the whole stack (max over layers) so
  # the scan stays homogeneous, then split each layer.
  flat = w.reshape((-1,) + w.shape[-2:])
  if cov is not None:
    covs = np.asarray(cov, np.float64)
    if covs.ndim == 2:
      covs = np.broadcast_to(covs, (flat.shape[0],) + covs.shape)
    else:
      covs = covs.reshape((-1,) + covs.shape[-2:])
    if covs.shape[0] != flat.shape[0]:
      raise ValueError(
          f"leaf {leaf.name!r}: {flat.shape[0]} stacked layers but "
          f"calibration has {covs.shape[0]} Gram matrices — layer-tagged "
          f"stats (dispatch.calibration_layer) are required per layer")
    whitened = [np.linalg.svd(_whitener(c).T @ np.asarray(m, np.float64),
                              compute_uv=False)
                for m, c in zip(flat, covs)]
    r = max(spec.pick(s) for s in whitened)
    fixed = dataclasses.replace(spec, fixed_rank=r, round_to=1)
    uvs = [activation_split(m, c, fixed)[:2] for m, c in zip(flat, covs)]
    us, vs = [u for u, _ in uvs], [v for _, v in uvs]
  else:
    svals = [np.asarray(jnp.linalg.svd(_host(m).astype(jnp.float32),
                                       compute_uv=False))
             for m in flat]
    r = max(spec.pick(s) for s in svals)
    us, vs = [], []
    for m in flat:
      u, v = balanced_split(m, r)
      us.append(u)
      vs.append(v)
  u = jnp.stack(us).reshape(w.shape[:-2] + us[0].shape)
  v = jnp.stack(vs).reshape(w.shape[:-2] + vs[0].shape)
  return FactoredLinear(w=None, u=u, v=v, name=leaf.name, group=leaf.group)


def factorize_leaf(leaf: FactoredLinear, rank: Optional[int] = None
                   ) -> FactoredLinear:
  """Stage-1 form: full-rank balanced split of an unfactored GEMM."""
  if leaf.is_factored:
    return leaf
  w = leaf.w
  if w.ndim == 2:
    u, v = balanced_split(w, rank)
  else:
    flat = w.reshape((-1,) + w.shape[-2:])
    uvs = [balanced_split(m, rank) for m in flat]
    u = jnp.stack([x for x, _ in uvs]).reshape(w.shape[:-2] + uvs[0][0].shape)
    v = jnp.stack([x for _, x in uvs]).reshape(w.shape[:-2] + uvs[0][1].shape)
  return FactoredLinear(w=None, u=u, v=v, name=leaf.name, group=leaf.group)


def collapse_leaf(leaf: FactoredLinear) -> FactoredLinear:
  """Inverse of factorize: materialize W = UV as an unfactored node."""
  if not leaf.is_factored:
    return leaf
  return FactoredLinear(w=leaf.product(), u=None, v=None,
                        name=leaf.name, group=leaf.group)


# -- tree-level drivers ------------------------------------------------------

def warmstart_tree(params: Any, spec: TruncationSpec) -> Any:
  """Stage-1 -> stage-2: truncate every factored GEMM in the tree."""
  return map_factored_leaves(lambda l: truncate_leaf(l, spec), params)


def factorize_tree(params: Any) -> Any:
  """Unfactored -> stage-1 full-rank factored (balanced SVD split)."""
  return map_factored_leaves(factorize_leaf, params)


def collapse_tree(params: Any) -> Any:
  """Factored -> unfactored (e.g. before export or re-factorization)."""
  return map_factored_leaves(collapse_leaf, params)
