"""Device program executions in the traced window per second of audio
served, summed over streams."""
from bench.readers import launches_per_audio_s as read  # noqa: F401
