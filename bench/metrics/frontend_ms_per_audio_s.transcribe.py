"""Host time (ms) in the speech fleet's conv frontend (the window's
`speech.frontend` spans: every slot's conv feed, its device round trips
included) per second of audio served, summed over streams. Read under
the profiler's Python tracer, whose cost is per Python call (see
`bench/program_spans.py`)."""
from bench.program_spans import ms_per_audio_s


def read(ctx):
  return ms_per_audio_s(ctx, "speech.frontend")
