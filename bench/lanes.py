"""PCG passes, counted from the answers the window returned.

A pass of the PCG loop body advances every active lane by one
iteration.  A served column admitted at engine tick ``a`` that took
``i`` iterations advanced ``min(k, i − (t − a)·k)`` of them in tick
``t`` (``k`` iterations per tick at most: the step loop runs while any
lane is active), and retired at tick ``a + max(⌈i / k⌉, 1) − 1``; the
engine stamps both ticks on each request, and a request whose stamps
disagree with its counts makes the count unreadable.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, Iterable, Optional


def tick_advances(sent: Iterable, k: int) -> Optional[Dict[int, list]]:
    """Per engine tick, the iterations each served column advanced in
    it (columns that advanced none are left out); None when a request's
    tick stamps disagree with its iteration counts."""
    per = defaultdict(list)
    for rec in sent:
        if rec.iters is None or rec.admit_tick < 0:
            continue
        a = int(rec.admit_tick)
        its = [int(i) for i in (rec.iters if hasattr(rec.iters, "__len__")
                                else [rec.iters])]
        if rec.finish_tick != a + max(max(-(-i // k) for i in its), 1) - 1:
            return None
        for i in its:
            t = a
            while i > 0:
                per[t].append(min(k, i))
                i -= k
                t += 1
    return per


def segment_counts(per: Dict[int, list], tick0: int, tick1: int) -> Dict:
    """Passes, lane-iterations and step calls of ticks ``[tick0,
    tick1)``."""
    passes = advances = steps = 0
    for t in range(tick0, tick1):
        adv = per.get(t, [])
        if adv:
            passes += max(adv)
            advances += sum(adv)
            steps += 1
    return {"passes": passes, "advances": advances, "steps": steps}
