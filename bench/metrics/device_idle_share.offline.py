"""1 - device busy / traced window, the busy time being the union of the
intervals in which an operation ran (mean over the chips)."""
from bench.readers import idle_share as read  # noqa: F401
