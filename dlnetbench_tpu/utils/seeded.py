"""The seeded generator every replayable plan draws from: splitmix64,
with the native tier's constants, so a seed means the same stream in
arrival plans, segment masks, the router, the tuner's candidate order
and the native tier's fault plans.
"""
from __future__ import annotations

import math

_M64 = (1 << 64) - 1


def splitmix64(state: int) -> tuple[int, int]:
    """One splitmix64 draw; returns ``(value, next_state)``.  Constants
    match the native tier (fault_plan.hpp:147) so a seed means the same
    stream on every tier."""
    state = (state + 0x9E3779B97F4A7C15) & _M64
    z = state
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _M64
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _M64
    return (z ^ (z >> 31)), state


class Rng:
    """Seeded splitmix64 stream with the native tier's u01 convention
    (``value >> 11`` over 2^53)."""

    def __init__(self, seed: int):
        self.state = seed & _M64

    def u01(self) -> float:
        v, self.state = splitmix64(self.state)
        return (v >> 11) / float(1 << 53)

    def uniform_int(self, lo: int, hi: int) -> int:
        """Inclusive [lo, hi]."""
        if hi <= lo:
            return lo
        v, self.state = splitmix64(self.state)
        return lo + v % (hi - lo + 1)

    def expovariate(self, rate: float) -> float:
        # 1 - u01() is in (0, 1]: log never sees 0
        return -math.log(1.0 - self.u01()) / rate
