"""Seeded DS2 weights, made by the benchmark on the device in one jitted call.

The weights belong to the benchmark, not to the program: the program is
handed them (`to_program`), and the plain reference reads the same arrays
(`bench.models.ds2_ref`), so the reference takes nothing the program made.

The init law follows the program's own (conv N(0, 0.05^2), LeCun-normal
GEMMs, zero GRU biases; a factored GEMM draws U and V with the balanced
scale that gives U @ V the dense variance), drawn with this module's own
keys. Forms:

  dense           every GEMM a full matrix (the served float tier)
  lowrank         GEMMs with both dims >= `lowrank_min_dim` as a rank-r
                  U, V pair, the rest dense (the paper's stage-2 form)
  factored_full   GEMMs with both dims >= `min_dim` as full-rank U, V
                  (the stage-1 trace-norm training form)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def jax_key(seed: int) -> jax.Array:
  """A PRNG key from any non-negative integer seed (wider than 32 bits)."""
  words = np.random.SeedSequence(int(seed)).generate_state(2)
  return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def gemm_shapes(config: dict) -> dict:
  """{name: (m, n)} of every GEMM of the model, in forward order."""
  freq = config["feat_dim"]
  for _ in range(2):
    freq = -(-freq // config["freq_stride"])
  prev = freq * config["conv_channels"]
  shapes = {}
  for i, h in enumerate(config["gru_dims"]):
    shapes[f"gru{i}/nonrec"] = (prev, 3 * h)
    shapes[f"gru{i}/rec"] = (h, 3 * h)
    prev = h
  shapes["fc"] = (prev, config["fc_dim"])
  shapes["out"] = (config["fc_dim"], config["vocab_size"])
  return shapes


def gemm_ranks(config: dict, form: str) -> dict:
  """{name: rank or None (dense)} for `form`."""
  ranks = {}
  for name, (m, n) in gemm_shapes(config).items():
    if form == "dense":
      ranks[name] = None
    elif form == "lowrank":
      big = min(m, n) >= config["lowrank_min_dim"]
      ranks[name] = config["rank"] if big else None
    elif form == "factored_full":
      ranks[name] = min(m, n) if min(m, n) >= config["min_dim"] else None
    else:
      raise ValueError(f"unknown weight form {form!r}")
  return ranks


def _spec(config: dict, form: str) -> tuple:
  ch = config["conv_channels"]
  (k1t, k1f), (k2t, k2f) = config["conv1_kernel"], config["conv2_kernel"]
  ranks = gemm_ranks(config, form)
  spec = [("conv1", (k1t, k1f, 1, ch), None),
          ("conv2", (k2t, k2f, ch, ch), None)]
  for name, (m, n) in gemm_shapes(config).items():
    spec.append((name, (m, n), ranks[name]))
  return tuple(spec)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, spec: tuple, dtype: str) -> dict:
  dt = jnp.dtype(dtype)
  keys = jax.random.split(key, len(spec))
  out = {}
  for k, (name, shape, rank) in zip(keys, spec):
    if name.startswith("conv"):
      out[name] = (jax.random.normal(k, shape, jnp.float32) * 0.05).astype(dt)
      continue
    m, n = shape
    if rank is None:
      w = jax.random.normal(k, (m, n), jnp.float32) * (1.0 / m) ** 0.5
      out[name] = {"w": w.astype(dt)}
    else:
      ku, kv = jax.random.split(k)
      s = ((1.0 / m) ** 0.5 / rank ** 0.5) ** 0.5
      out[name] = {
          "u": (jax.random.normal(ku, (m, rank), jnp.float32) * s).astype(dt),
          "v": (jax.random.normal(kv, (rank, n), jnp.float32) * s).astype(dt)}
    if name.endswith("/rec"):
      out[name.replace("/rec", "/bias")] = jnp.zeros((n,), jnp.float32)
  return out


def make(config: dict, seed: int, form: str) -> dict:
  """Flat {name: array or {"w"} / {"u", "v"}} weights on the default device."""
  return _make(jax_key(seed), _spec(config, form), config["dtype"])


def to_program(flat: dict, config: dict) -> dict:
  """The program's DS2 parameter tree over the same arrays (no copies)."""
  from repro.core.factored import FactoredLinear

  def leaf(name: str) -> FactoredLinear:
    group = "rec" if name.endswith("/rec") else "nonrec"
    g = flat[name]
    return FactoredLinear(w=g.get("w"), u=g.get("u"), v=g.get("v"),
                          name=name, group=group)

  grus = {}
  for i in range(len(config["gru_dims"])):
    grus[f"gru{i}"] = {"nonrec": leaf(f"gru{i}/nonrec"),
                       "rec": leaf(f"gru{i}/rec"),
                       "bias": flat[f"gru{i}/bias"]}
  return {"conv1": flat["conv1"], "conv2": flat["conv2"], "grus": grus,
          "fc": leaf("fc"), "out": leaf("out")}
