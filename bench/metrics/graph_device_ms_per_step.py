"""Device-busy time inside each ``bench.step`` span, mean over steps (and
over the chips): the fused graph with its input stack and stats."""
from bench.readers import device_ms_per_step as read  # noqa: F401
