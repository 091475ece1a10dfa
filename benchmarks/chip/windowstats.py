"""What a measured window recorded, and the arithmetic the metric readers
share: whole-round rates and nearest-rank percentiles."""
from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass
class RoundRecord:
    """One round the window ran: host-clock start and end, the units of
    work it completed (``{"queries": 2}``), and the latency of each item
    it served, by kind."""
    start: float
    end: float
    units: dict
    latencies: dict


@dataclasses.dataclass
class Window:
    start: float
    rounds: list

    @property
    def end(self) -> float:
        return self.rounds[-1].end if self.rounds else self.start

    @property
    def seconds(self) -> float:
        """From the window's start to the end of its last round."""
        return self.end - self.start

    def total(self, unit: str) -> float:
        return sum(r.units.get(unit, 0) for r in self.rounds)

    def latencies(self, kind: str) -> list:
        return [x for r in self.rounds for x in r.latencies.get(kind, ())]

    def rate(self, unit: str):
        """Units completed over the wall time of the whole rounds."""
        return self.total(unit) / self.seconds if self.seconds > 0 else None


def nearest_rank(values, pct: float):
    """The ``pct`` percentile by nearest rank: the smallest value with at
    least ``pct`` percent of the values at or below it."""
    if not values:
        return None
    ordered = sorted(values)
    return ordered[max(0, math.ceil(pct / 100 * len(ordered)) - 1)]
