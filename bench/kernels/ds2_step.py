"""Model operations of the DS2 acoustic model, from its shapes.

Counted: multiply-adds of the two convolutions and of every GEMM, as
2 operations each, over the frames the model must compute for an
utterance (`conv` output frames at ceil(t / stride) per stage).
Not counted: gates, activations, log-softmax and padding, nor any
recomputation. A factored GEMM costs r (m + n) multiply-adds per row.
"""
from __future__ import annotations

from bench.models import ds2_weights


def frames_after(config: dict, raw: int) -> tuple:
  """(conv1 output frames, conv2 output frames) for `raw` mel frames."""
  t1 = -(-raw // config["conv1_time_stride"])
  return t1, -(-t1 // config["time_stride"])


def conv_flops(config: dict, raw: int) -> float:
  ch = config["conv_channels"]
  f1 = -(-config["feat_dim"] // config["freq_stride"])
  f2 = -(-f1 // config["freq_stride"])
  (k1t, k1f), (k2t, k2f) = config["conv1_kernel"], config["conv2_kernel"]
  t1, t2 = frames_after(config, raw)
  return 2.0 * (t1 * f1 * k1t * k1f * ch + t2 * f2 * k2t * k2f * ch * ch)


def gemm_macs_per_frame(config: dict, form: str) -> float:
  """Multiply-adds of all GEMMs for one post-frontend frame of one stream."""
  ranks = ds2_weights.gemm_ranks(config, form)
  total = 0
  for name, (m, n) in ds2_weights.gemm_shapes(config).items():
    r = ranks[name]
    total += m * n if r is None else r * (m + n)
  return float(total)


def forward_flops(config: dict, form: str, raw: int) -> float:
  """Forward operations for one utterance of `raw` mel frames."""
  if raw <= 0:
    return 0.0
  _, t2 = frames_after(config, raw)
  return conv_flops(config, raw) + 2.0 * t2 * gemm_macs_per_frame(config,
                                                                   form)


def train_flops(config: dict, form: str, raw: int) -> float:
  """Forward plus backward operations for one training utterance: the
  backward pass costs two forward passes (gradients of the inputs and of
  the weights). The first conv needs no input gradient, which this
  count ignores (under 1% of the total at the published widths)."""
  return 3.0 * forward_flops(config, form, raw)
