"""Operation and byte counts of the kernels and of the DS2 model step,
against counts by hand, and the peaks table."""
from __future__ import annotations

import pytest

from bench import harness
from bench.kernels import ds2_step, roofline
from bench.peaks import peaks_for

V5E = peaks_for("TPU v5 lite")


def kernel(name):
  return harness.Cell("ds2-wsj.live16").kernel(name)


def test_decode_matvec_counts():
  ops, b = kernel("decode_matvec").cost(16, 1280, 3840)
  assert ops == 2 * 16 * 1280 * 3840
  assert b == 2 * (1280 * 3840 + 16 * 1280 + 16 * 3840)
  t, bound = roofline.least_seconds(ops, b, V5E)
  assert bound == "memory" and t == pytest.approx(b / 819e9)


def test_gru_cell_counts():
  ops, b = kernel("gru_cell").cost(16, 1024)
  assert ops == 2 * 16 * 1024 * 3072 + 10 * 16 * 3072
  # U, xw, h twice, h', bf16; bias f32
  assert b == 2 * (1024 * 3072 + 16 * 3072 + 2 * 16 * 1024 + 16 * 1024) \
      + 4 * 3072


def test_lowrank_gemm_counts():
  ops, b = kernel("lowrank_gemm").cost(16, 1280, 256, 3840)
  assert ops == 2 * 16 * 256 * (1280 + 3840)
  assert b == 2 * (256 * (1280 + 3840) + 16 * 1280 + 16 * 3840)


def test_compute_bound_at_large_batch():
  ops, b = kernel("decode_matvec").cost(4096, 4096, 4096)
  assert roofline.least_seconds(ops, b, V5E)[1] == "compute"


def test_ds2_model_step_by_hand():
  cfg = harness.Cell("ds2-wsj.live16").config
  # 100 mel frames -> 50 after conv1 -> 25 after conv2
  assert ds2_step.frames_after(cfg, 100) == (50, 25)
  assert ds2_step.frames_after(cfg, 101) == (51, 26)
  conv = 2 * (50 * 40 * 11 * 41 * 32 + 25 * 20 * 11 * 21 * 32 * 32)
  assert ds2_step.conv_flops(cfg, 100) == conv
  gemm = (640 * 2304 + 768 * 2304 + 768 * 3072 + 1024 * 3072 +
          1024 * 3840 + 1280 * 3840 + 1280 * 1536 + 1536 * 32)
  assert ds2_step.gemm_macs_per_frame(cfg, "dense") == gemm
  assert ds2_step.forward_flops(cfg, "dense", 100) == conv + 2 * 25 * gemm
  assert ds2_step.train_flops(cfg, "dense", 100) == 3 * (conv + 2 * 25 * gemm)
  assert ds2_step.forward_flops(cfg, "dense", 0) == 0


def test_ds2_lowrank_and_stage1_forms_by_hand():
  cfg = harness.Cell("ds2-wsj-r256.live16").config
  r = 256
  lowrank = (r * (640 + 2304) + r * (768 + 2304) + r * (768 + 3072) +
             r * (1024 + 3072) + r * (1024 + 3840) + r * (1280 + 3840) +
             r * (1280 + 1536) + 1536 * 32)
  assert ds2_step.gemm_macs_per_frame(cfg, "lowrank") == lowrank
  dense = ds2_step.gemm_macs_per_frame(cfg, "dense")
  assert 19.5e6 < dense < 19.7e6 and 6.8e6 < lowrank < 7.0e6
  s1 = harness.Cell("ds2-wsj.train32").config
  full = sum(min(m, n) * (m + n) for m, n in [
      (640, 2304), (768, 2304), (768, 3072), (1024, 3072), (1024, 3840),
      (1280, 3840), (1280, 1536), (1536, 32)])
  assert ds2_step.gemm_macs_per_frame(s1, "factored_full") == full


def test_unknown_device_kind_raises():
  with pytest.raises(KeyError, match="no peaks"):
    peaks_for("TPU v99")
  assert V5E["bf16_flops"] == 197e12 and V5E["hbm_bytes_per_s"] == 819e9
