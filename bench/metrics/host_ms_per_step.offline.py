"""Host time per engine step: the ``bench.step`` span around
`EngineCore.step` less the device-busy time inside it, mean over steps."""
from bench.readers import host_ms_per_step as read  # noqa: F401
