"""Arrival process ``backlog``: closed, at capacity.

    {"kind": "backlog", "queue_per_lane": 1}

Before every engine round the queue is topped up to ``queue_per_lane`` x
the cell's lanes, so the engine never waits for work and the cell
measures retired requests per second.  A request is due when it is
submitted.
"""


class Schedule:
    def __init__(self, params: dict, rng, seconds: float, lanes_total: int):
        self.target = int(params["queue_per_lane"]) * lanes_total

    def take(self, t: float, queue_depth: int) -> list:
        return [t] * max(0, self.target - queue_depth)

    def next_due(self) -> float:
        return 0.0
