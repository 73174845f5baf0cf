"""Share (%) of the fleet's slots that were live in the window's masked
frame steps: summed `live` of its `speech.frame_step` spans over (steps
x the mix's `slots`). A count, so the Python tracer does not inflate it."""
from bench.program_spans import slot_occupancy as read  # noqa: F401
