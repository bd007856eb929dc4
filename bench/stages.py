"""Per-step host time of one program stage, from the engine's counters.

`EngineCore.stats()["host_stages"]` holds, per named span of the program
(``engine.*``, ``snn.*``), the cumulative host seconds, calls and longest
call. `ms_per_step` reads its change over the window, taken before and
after it (``ctx.engine``), the way `slot_occupancy` reads its deltas.
A program without the counters, or a window without steps, reads None.
"""
from __future__ import annotations

from typing import Optional


def ms_per_step(ctx, name: str) -> Optional[float]:
    """1000 x the stage's host seconds over the window / the engine steps
    run in it."""
    before, after = ctx.engine
    steps = after["steps_run"] - before["steps_run"]
    end = after.get("host_stages", {}).get(name)
    if end is None or steps <= 0:
        return None
    start = before.get("host_stages", {}).get(name, {"seconds": 0.0})
    return 1000.0 * (end["seconds"] - start["seconds"]) / steps
