"""Host time (ms) in the speech fleet's masked frame steps (the window's
`speech.frame_step` spans: build the step's input, dispatch, fetch the
log-probs) per second of audio served, summed over streams. Read under
the profiler's Python tracer, whose cost is per Python call (see
`bench/program_spans.py`)."""
from bench.program_spans import ms_per_audio_s


def read(ctx):
  return ms_per_audio_s(ctx, "speech.frame_step")
