"""The control (the cell's numbers one precision step below bf16, as
`bench/control.py` reads them on the chip) at smoke widths on the CPU:
it runs through the same harness path and reads at least three times
what the sound program reads on the number that separates them. The
limits themselves come from the chip readings at the cells' own sizes
(PERF.md); at smoke widths only the separation is checked."""
from __future__ import annotations

import pytest

from bench import control
from bench.tests.conftest import SHORT, smoke_cell

SEED = 2 ** 33 + 4242
SEPARATES = {"ds2-wsj.live16": "logprob_max_err_nats",
             "ds2-wsj-r256.live16": "logprob_max_err_nats",
             "ds2-wsj.transcribe64": "logprob_max_err_nats",
             "ds2-wsj.train32": "first_loss_rel_gap"}


@pytest.mark.parametrize("name", sorted(SEPARATES))
def test_control_reads_apart_from_the_program(name):
  cell = smoke_cell(name, **SHORT[name.split(".", 1)[1]])
  sound = control.readings(cell, SEED, 3, False, require_accelerator=False)
  ctl = control.readings(cell, SEED, 3, True, require_accelerator=False)
  key = SEPARATES[name]
  assert ctl[key] >= 3 * sound[key], (sound, ctl)
