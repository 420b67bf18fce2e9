"""The window's rule, the same for every driver.

Work is started while less than `seconds` has passed since the window opened;
what was started is finished; the denominator of every rate is the time that
had passed when the last item finished. So a rate, or a time per item, never
moves in steps of one item as the window's length changes: one more item in
the window adds its own time to the denominator.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional


@dataclass
class Item:
    """One plan or one request of the window, on the host's monotonic clock."""

    start: float
    end: float
    ok: bool
    answer: Any = None  # what the system said, for the comparison
    spans: Any = None  # the item's span tree in a traced run
    info: dict = field(default_factory=dict)


@dataclass
class Window:
    opened: float
    closed: float  # when the last item finished
    items: List[Item]

    @property
    def elapsed(self) -> float:
        return self.closed - self.opened

    @property
    def attempted(self) -> int:
        return len(self.items)

    @property
    def failed(self) -> int:
        return sum(1 for it in self.items if not it.ok)

    def rate(self) -> float:
        """Items that succeeded per second of the time that really passed."""
        return (self.attempted - self.failed) / self.elapsed

    def seconds_per_item(self) -> float:
        return self.elapsed / self.attempted

    def quantile(self, q: float) -> Optional[float]:
        """Nearest-rank quantile of every item's latency (server/loadgen.py's
        `_quantile`): the tail is the tail of all the window's items."""
        vals = sorted(it.end - it.start for it in self.items)
        if not vals:
            return None
        return vals[min(len(vals) - 1, max(0, int(math.ceil(q * len(vals))) - 1))]


def run_window(seconds: float, one: Callable[[int], Item],
               clock: Callable[[], float] = time.monotonic,
               limit: Optional[int] = None) -> Window:
    """Drive `one(i)` back to back under the rule above. At least one item
    runs, and at most `limit` where one is given (a traced window)."""
    opened = clock()
    items: List[Item] = []
    while True:
        items.append(one(len(items)))
        if clock() - opened >= seconds or len(items) == limit:
            break
    return Window(opened=opened, closed=items[-1].end, items=items)
