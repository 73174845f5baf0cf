"""Mean host time (ms) per training step: each `train.step` span of the
window less its `train.wait`, the wait for the device to finish the
step. The dispatch and the metrics' transfer after the wait are counted.
Read under the profiler's Python tracer, whose cost is per Python call
(see `bench/program_spans.py`)."""
from bench.program_spans import host_ms_per_step as read  # noqa: F401
