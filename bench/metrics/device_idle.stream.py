"""Share (%) of the host's time inside `process_chunk` (the server's
calls, flushes included) in which no operation ran on the device. The
window of a live cell is paced by the audio clock, so idle over the whole
window reads 1 - device work / audio time whatever the host does; inside
the calls it moves with the host loop."""
from bench.readers import idle_within_pct


def read(ctx):
  return idle_within_pct(ctx, "bench.process_chunk")
