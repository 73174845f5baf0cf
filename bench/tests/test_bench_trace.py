"""The trace reduction on a small trace recorded on a TPU v5e: three
`process_chunk` calls of 16 lockstep DS2 streams (ds2-wsj weights, the
serving policy of `ds2-wsj.live16`) inside one "bench.round" span."""
from __future__ import annotations

import gzip
import pathlib

import pytest

from bench import harness, readers, tracereduce
from bench.peaks import peaks_for

DATA = pathlib.Path(__file__).parent / "data"


@pytest.fixture(scope="module")
def summary():
  import jax
  raw = gzip.decompress((DATA / "live16_3chunks.xplane.pb.gz").read_bytes())
  profile = jax.profiler.ProfileData.from_serialized_xspace(raw)
  return tracereduce.reduce_profile(profile, window_span="bench.round")


def ctx_for(summary, cell="ds2-wsj.live16"):
  return harness_ctx(harness.Cell(cell), summary)


def harness_ctx(cell, summary):
  from bench.run import ReadContext
  return ReadContext(cell, {"audio_s": 3 * 16 * 16 / 100.0}, summary,
                     peaks_for("TPU v5 lite"), {})


def test_window_and_busy(summary):
  assert summary.devices == 1
  assert summary.window_s == pytest.approx(0.232494537)
  assert 0 < summary.busy_s < 0.05 * summary.window_s
  idle = readers.idle_pct(ctx_for(summary))
  assert 95.0 < idle < 100.0


def test_program_executions(summary):
  by_program = {}
  for name, n in summary.module_counts.items():
    prog = name.split("(")[0]
    by_program[prog] = by_program.get(prog, 0) + n
  # 3 chunks x 4 post-frontend frames; per slot, one call of each conv
  # stage per chunk and one more at the final flush (one conv1 call at the
  # span's start falls outside it by the host/device clock offset)
  assert by_program["jit_frame_step"] == 12
  assert 4 * 16 * 2 - 2 <= by_program["jit_conv1"] + by_program[
      "jit_conv2"] <= 4 * 16 * 2
  assert summary.launches == sum(by_program.values())
  launches = readers.launches_per_audio_s(ctx_for(summary))
  assert launches == pytest.approx(summary.launches / 7.68)


def test_kernel_events_and_shapes(summary):
  grus = [c.event for c in tracereduce.kernel_calls(summary, "gru_cell")]
  mats = [c.event for c in tracereduce.kernel_calls(summary,
                                                    "decode_matvec")]
  assert len(grus) == 12 * 3 and len(mats) == 12 * 4
  assert tracereduce.kernel_calls(summary, "lowrank_gemm") == []
  hidden = sorted({tracereduce.hlo_shapes(e.name)[0][1] for e in grus})
  assert hidden == [(16, 768), (16, 1024), (16, 1280)]
  for e in mats:
    out, ins = tracereduce.hlo_shapes(e.name)
    assert len(ins) == 2 and ins[0][1][0] == 16
    assert ins[0][1][1] == ins[1][1][0] and out[1][1] == ins[1][1][1]


@pytest.mark.parametrize("kernel", ["gru_cell", "decode_matvec"])
def test_rooflines_are_shares(summary, kernel):
  value = readers.kernel_roofline(ctx_for(summary), kernel)
  assert 0.0 < value <= 100.0


def test_kernel_calls_take_their_staging(summary):
  """The first layer's `gru_cell` (H 768) reads its recurrent weight
  U (768, 3 * 768) from on-chip memory: XLA copied it there from HBM and
  re-laid it out first, with copies that hold the core. The call is
  charged from the first of those copies, with U's bytes."""
  calls = [c for c in tracereduce.kernel_calls(summary, "gru_cell")
           if tracereduce.hlo_shapes(c.event.name)[0][1] == (16, 768)]
  assert len(calls) == 12
  u_bytes = 2 * 768 * 3 * 768
  for c in calls:
    assert u_bytes <= c.staged_bytes <= u_bytes + 2 * 16 * 768 * 4
    held = c.event.end - c.event.start
    assert 2 * held < c.event.end - c.start < 10 * held
  # a decode_matvec whose weight XLA slices in asynchronously is charged
  # from the first slice, with the whole weight's bytes
  mats = {tracereduce.hlo_shapes(c.event.name)[1][1][1]: c
          for c in tracereduce.kernel_calls(summary, "decode_matvec")}
  assert mats[(768, 3072)].staged_bytes == 2 * 768 * 3072
  assert mats[(768, 3072)].start < mats[(768, 3072)].event.start


def test_staging_resolves_copies_slices_and_bitcasts():
  ev = tracereduce.Event
  hbm, vmem = "{1,0:T(8,128)(2,1)}", "{1,0:T(8,128)(2,1)S(1)}"
  evs = [
      ev(f"%slice-start.1 = ((bf16[64,256]{hbm}), bf16[32,256]{vmem}, "
         f"s32[]{{:S(2)}}) async-start(bf16[64,256]{hbm} %w.1), "
         f"calls=%async_computation.1", 0, 10),
      ev(f"%slice-done.1 = bf16[32,256]{vmem} async-done(((bf16[64,256]"
         f"{hbm}), bf16[32,256]{vmem}, s32[]{{:S(2)}}) %slice-start.1)",
         100, 100),
      ev(f"%copy.1 = bf16[256,32]{vmem} copy(bf16[32,256]{vmem} "
         f"%slice-done.1)", 100, 150),
      ev(f"%k.1 = bf16[8,32]{vmem} custom-call(bf16[8,256]{hbm} %x, "
         f"bf16[32,8,32]{vmem} %bitcast.3), custom_call_target="
         f"\"tpu_custom_call\"", 150, 170),
  ]
  s = tracereduce.Summary(window=(0, 200), devices=1, busy_s=0, window_s=0,
                          op_seconds={}, op_counts={}, module_counts={},
                          idle_gaps=[], ops=evs, plane_ops={"d": evs},
                          plane_modules={"d": [ev("jit_f", 0, 200)]})
  (call,) = tracereduce.kernel_calls(s, "k")
  assert call.start == 0 and call.staged_bytes == 2 * 32 * 256
  assert tracereduce.hlo_parts(evs[2].name)[1] == "copy"


def test_idle_inside_the_server_calls(summary):
  busy, total = tracereduce.busy_within(summary, "bench.process_chunk")
  assert 0 < busy < total <= summary.window_s
  assert busy == pytest.approx(summary.busy_s, rel=0.05)
  idle = readers.idle_within_pct(ctx_for(summary), "bench.process_chunk")
  assert idle == pytest.approx(100 * (1 - busy / total))
  assert readers.idle_within_pct(ctx_for(summary), "bench.nothing") is None


def test_absent_kernel_reads_nothing(summary):
  assert readers.kernel_roofline(ctx_for(summary), "lowrank_gemm") is None


def test_breakdown(summary):
  b = summary.breakdown()
  assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
  secs = [s for _, s in b["device_ops"]]
  assert secs == sorted(secs, reverse=True)
  assert all(isinstance(n, str) and s > 0 for n, s in b["device_ops"])
  assert {n for n, _ in b["idle_gaps"]} <= {"bench.process_chunk",
                                           "outside any bench span"}


def test_self_time_of_nested_ops():
  ev = tracereduce.Event
  outer = ev("%while.1 = ...", 0, 100)
  inner = [ev("%fusion.1 = ...", 10, 30), ev("%fusion.2 = ...", 40, 70)]
  deeper = ev("%fusion.3 = ...", 45, 50)
  tracereduce._nest([outer, deeper] + inner)
  assert outer.self_ns == 100 - 20 - 30
  assert inner[1].self_ns == 30 - 5 and deeper.self_ns == 5


def test_hlo_operand_memory_spaces():
  name = ("%decode_matvec.6 = bf16[16,3840]{1,0:T(8,128)(2,1)S(1)} "
          "custom-call(bf16[16,1024]{1,0:T(8,128)(2,1)S(1)} %gru_cell.4, "
          "bf16[1024,3840]{1,0:T(8,128)(2,1)} %w), custom_call_target="
          "\"tpu_custom_call\", operand_layout_constraints={bf16[16,1024]"
          "{1,0}, bf16[1024,3840]{1,0}}")
  out, ins = tracereduce.hlo_shapes(name)
  assert out == ("bf16", (16, 3840), 1)
  assert ins == [("bf16", (16, 1024), 1), ("bf16", (1024, 3840), 0)]
  from bench.kernels import roofline
  assert roofline.hbm_bytes(out, ins) == 2 * 1024 * 3840
