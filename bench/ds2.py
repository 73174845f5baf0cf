"""What the DS2 drivers share: the program's config checked against the
benchmark's file, the routing each GEMM must take, the served-token
comparison with the reference, and the seeded weights handed over."""
from __future__ import annotations

import numpy as np

from bench import harness
from bench.harness import BenchError, Check
from bench.models import ds2_weights

# Config-file keys that must equal the program's ModelConfig field.
_FIELDS = ("feat_dim", "gru_dims", "fc_dim", "vocab_size", "conv_channels",
           "time_stride")
LANE = 128              # narrowest GEMM dim a Pallas kernel takes
DECODE_BATCH_MAX = 16   # widest batch decode_matvec takes


def program_config(config: dict):
  """The program's ModelConfig for `config`, checked key by key."""
  import jax.numpy as jnp

  from repro import configs
  get = (configs.get_smoke if config.get("program_preset") == "smoke"
         else configs.get_config)
  cfg = get(config["program_config"])
  for key in _FIELDS:
    got = getattr(cfg, key)
    got = list(got) if isinstance(got, tuple) else got
    if got != config[key]:
      raise BenchError(f"program config {cfg.name}: {key} = {got}, the "
                       f"benchmark's file says {config[key]}")
  if jnp.dtype(cfg.dtype).name != config["dtype"]:
    raise BenchError(f"program dtype {jnp.dtype(cfg.dtype).name} != "
                     f"{config['dtype']}")
  return cfg


def program_params(config: dict, seed: int, form: str, variant: str = ""):
  """(flat bench weights, the program's tree over them). `variant`
  "int8" quantises the tree with the program's own PTQ (the control)."""
  flat = ds2_weights.make(config, seed, form)
  params = ds2_weights.to_program(flat, config)
  if variant == "int8":
    from repro.quant import quantize_params
    params = quantize_params(params)
  elif variant:
    raise BenchError(f"unknown variant {variant!r}")
  return flat, params


def expected_regimes(config: dict, form: str, slots: int) -> dict:
  """{GEMM name: regime} that the serving policy must route to at
  `slots` concurrent streams: a factored GEMM to lowrank_gemm, a dense
  recurrent one to the fused gru_cell, another dense one to
  decode_matvec while the step's batch fits it, and any GEMM with a
  dimension under the 128-lane tile to plain jnp."""
  ranks = ds2_weights.gemm_ranks(config, form)
  out = {}
  for name, (m, n) in ds2_weights.gemm_shapes(config).items():
    r = ranks[name]
    if min(m, n, r or m) < LANE:
      out[name] = "jnp"
    elif r is not None:
      out[name] = "lowrank_gemm"
    elif name.endswith("/rec"):
      out[name] = "gru_cell"
    elif slots <= DECODE_BATCH_MAX:
      out[name] = "decode_matvec"
    else:
      out[name] = "jnp"
  return out


def check_routing(records, expected: dict) -> dict:
  """Raise unless every traced GEMM took its expected regime; returns
  {regime: count}."""
  seen = {}
  for name, regime in set(records):
    if expected.get(name) != regime:
      raise BenchError(f"GEMM {name!r} routed to {regime!r}; the cell "
                       f"expects {expected.get(name)!r}")
    seen[name] = regime
  missing = sorted(set(expected) - set(seen))
  if missing:
    raise BenchError(f"GEMMs {missing} were never routed")
  counts = {}
  for regime in seen.values():
    counts[regime] = counts.get(regime, 0) + 1
  return counts


def served_gap(ref_lp: np.ndarray, served_lp: np.ndarray) -> float:
  """Widest gap, in nats, by which the served (argmax) label of a frame
  lies below the reference's best label of that frame."""
  ref_lp = np.asarray(ref_lp, np.float64)
  pick = np.asarray(served_lp).argmax(-1)
  chosen = np.take_along_axis(ref_lp, pick[..., None], -1)[..., 0]
  return float(np.max(ref_lp.max(-1) - chosen))


def compare_streams(config: dict, flat_ref: dict, items: list,
                    limits: dict, min_frames: int, block: int = 16,
                    bucket: int = 512) -> list:
  """Checks of served log-probs against the reference: the widest gap
  of a served label below the reference's best, and the largest error of
  any label's log-prob (both in nats, over every compared frame).

  `items`: [(feats (t, f), served log-probs (t', v))]. The reference runs
  in blocks of `block` rows, zero-padded to a multiple of `bucket` frames
  (its `lengths` masking keeps each row exact), longest first."""
  import jax

  from bench.kernels import ds2_step
  from bench.models import ds2_ref
  items = sorted(items, key=lambda it: -it[0].shape[0])
  forward = jax.jit(lambda w, f, n: ds2_ref.forward(w, f, config, lengths=n))
  gap, err, frames, bad_len, bad_val = 0.0, 0.0, 0, 0, 0
  for b0 in range(0, len(items), block):
    group = items[b0:b0 + block]
    t_max = -(-group[0][0].shape[0] // bucket) * bucket
    feats = np.zeros((block, t_max, group[0][0].shape[1]), np.float32)
    lengths = np.zeros((block,), np.int32)
    for i, (f, _) in enumerate(group):
      feats[i, :f.shape[0]] = f
      lengths[i] = f.shape[0]
    ref = np.asarray(jax.device_get(forward(flat_ref, feats,
                                            np.maximum(lengths, 1))))
    for i, (f, lp) in enumerate(group):
      r = ref[i, :ds2_step.frames_after(config, f.shape[0])[1]]
      if lp.shape != r.shape:
        bad_len += 1
        continue
      if not np.isfinite(lp).all():
        bad_val += 1
        continue
      gap = max(gap, served_gap(r, lp))
      err = max(err, float(np.max(np.abs(np.asarray(lp, np.float64) - r))))
      frames += lp.shape[0]
  return harness.compared(
      {"served_gap_nats": gap, "logprob_max_err_nats": err}, limits,
      [Check("frames_compared", frames, min_frames, ok=frames >= min_frames),
       Check("wrong_length_streams", bad_len, 0),
       Check("nonfinite_streams", bad_val, 0)])
