"""The one traffic generator: a traffic mix is a data file of
parameters (``bench/traffic/<name>.json``) that this module reads.

Keys of a mix:

* ``path``: ``"served"`` — requests go to ``SolveFrontend.submit`` (the
  frontend and its ``SolveEngine``); ``"direct"`` — one caller of the
  library path ``PreconditionerHandle.solve``.
* ``loop``: ``"closed"`` — ``clients`` callers, each sending its next
  request as soon as it holds the last one's result (a request is due
  when it is sent); ``"open"`` — requests due at seeded Poisson
  arrivals of ``rate_per_s`` for the whole window, each timed from when
  it was due (served path only).
* ``columns``: right-hand sides per request, drawn uniformly from the
  list for each request.

Right-hand sides and arrivals come from ``--seed`` alone: request
``seq`` of client ``c`` always gets the same columns, drawn standard
normal and projected to mean zero, in the graph's original labels.
"""
from __future__ import annotations

import json
import time
from concurrent.futures import FIRST_COMPLETED, wait
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, List, Optional

import numpy as np

KEYS = {"path", "loop", "clients", "columns", "rate_per_s"}


def load_mix(path) -> dict:
    return load_mix_dict(json.loads(Path(path).read_text()), path)


def load_mix_dict(mix: dict, path="traffic mix") -> dict:
    """Validate a mix's keys and fill in defaults."""
    mix = dict(mix)
    unknown = set(mix) - KEYS
    if unknown:
        raise ValueError(f"{path}: unknown traffic keys {sorted(unknown)}")
    if mix.get("path") not in ("served", "direct"):
        raise ValueError(f"{path}: path must be 'served' or 'direct'")
    if mix.get("loop") not in ("closed", "open"):
        raise ValueError(f"{path}: loop must be 'closed' or 'open'")
    if mix["loop"] == "closed" and int(mix.get("clients", 0)) < 1:
        raise ValueError(f"{path}: a closed loop needs clients >= 1")
    if mix["loop"] == "open" and float(mix.get("rate_per_s", 0)) <= 0:
        raise ValueError(f"{path}: an open loop needs rate_per_s > 0")
    if mix["path"] == "direct" and (mix["loop"] != "closed"
                                    or int(mix["clients"]) != 1):
        raise ValueError(f"{path}: the direct path has one closed-loop "
                         f"caller")
    cols = mix.get("columns", [1])
    if not cols or any(int(c) < 1 for c in cols):
        raise ValueError(f"{path}: columns must list counts >= 1")
    mix["columns"] = [int(c) for c in cols]
    return mix


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2 ** 63, *stream])


def rhs(seed: int, n: int, columns: int, *stream: int) -> np.ndarray:
    """Seeded right-hand side(s), mean zero: ``(n,)`` for one column,
    else ``(columns, n)``."""
    b = _rng(seed, *stream).standard_normal(
        n if columns == 1 else (columns, n)).astype(np.float32)
    return b - b.mean(axis=-1, keepdims=True)


def request_rhs(mix: dict, seed: int, n: int, client: int,
                seq: int) -> np.ndarray:
    cols = mix["columns"]
    k = cols[int(_rng(seed, 1, client, seq).integers(len(cols)))]
    return rhs(seed, n, k, 0, client, seq)


def arrivals(mix: dict, seed: int, seconds: float) -> List[float]:
    """Open loop: due times (seconds into the window), seeded Poisson."""
    rng, rate, out, t = _rng(seed, 2), float(mix["rate_per_s"]), [], 0.0
    while True:
        t += float(rng.exponential(1.0 / rate))
        if t >= seconds:
            return out
        out.append(t)


@dataclass(eq=False)
class Sent:
    """One request as its client saw it, and what came back."""
    client: int
    seq: int
    b: np.ndarray                 # original labels
    t_due: float
    t_send: float
    t_done: Optional[float] = None
    x: Optional[np.ndarray] = None  # original labels
    iters: Optional[np.ndarray] = None
    relres: Optional[np.ndarray] = None  # the program's own
    status: str = "missing"
    admit_tick: int = -1
    finish_tick: int = -1

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_due


class Segment:
    """Calls ``start()`` once the window is ``at_s`` seconds old and
    ``stop()`` ``length_s`` seconds after that (a traced part of the
    window).  Either may be None."""

    def __init__(self, at_s: float = 0.0, length_s: float = 0.0,
                 start: Optional[Callable] = None,
                 stop: Optional[Callable] = None):
        self.at_s, self.length_s = at_s, length_s
        self._start, self._stop = start, stop
        self.started_at: Optional[float] = None
        self.done = start is None

    def poll(self, elapsed: float) -> None:
        if self.done:
            return
        if self.started_at is None and elapsed >= self.at_s:
            self._start()
            self.started_at = elapsed
        elif self.started_at is not None \
                and elapsed >= self.started_at + self.length_s:
            self._stop()
            self.done = True

    def finish(self) -> None:
        if not self.done and self.started_at is not None:
            self._stop()
        self.done = True


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def drive_served(mix: dict, seed: int, n: int, seconds: float,
                 submit: Callable, collect: Callable, *, grace_s: float,
                 segment: Segment, clock=time.perf_counter) -> List[Sent]:
    """Drive the served path for ``seconds``: ``submit(b)`` returns a
    future, ``collect(sent, result)`` fills a :class:`Sent` from it.
    Requests due in the window are all sent; the run then waits for
    their answers until ``grace_s`` past the window's close, and those
    that never come stay ``"missing"``."""
    closed = mix["loop"] == "closed"
    due = [] if closed else arrivals(mix, seed, seconds)
    pending, out = {}, []
    t0 = clock()

    def issue(client: int, seq: int, t_due: float) -> None:
        with _annotate("client/make_rhs"):
            b = request_rhs(mix, seed, n, client, seq)
        with _annotate("client/submit"):
            t_send = clock()
            fut = submit(b)
        rec = Sent(client, seq, b, t_due if t_due else t_send, t_send)
        pending[fut] = rec
        out.append(rec)

    if closed:
        for c in range(int(mix["clients"])):
            issue(c, 0, 0.0)
    nxt = 0
    while True:
        now = clock()
        segment.poll(now - t0)
        while nxt < len(due) and t0 + due[nxt] <= now:
            issue(nxt, 0, t0 + due[nxt])
            nxt += 1
        if not pending and nxt >= len(due):
            break
        if now > t0 + seconds + grace_s:
            break
        step = 0.25 if nxt >= len(due) else \
            min(0.25, max(t0 + due[nxt] - now, 0.0))
        with _annotate("client/await"):
            done, _ = wait(list(pending), timeout=step,
                           return_when=FIRST_COMPLETED)
        t = clock()
        for fut in done:
            rec = pending.pop(fut)
            rec.t_done = t
            collect(rec, fut.result())
            if closed and t < t0 + seconds:
                issue(rec.client, rec.seq + 1, 0.0)
    segment.finish()
    return out


def drive_direct(mix: dict, seed: int, n: int, seconds: float,
                 solve: Callable, collect: Callable, *, segment: Segment,
                 clock=time.perf_counter) -> List[Sent]:
    """One closed-loop caller of ``solve(b)`` for ``seconds``: each
    answer is collected onto the host, and its latency taken, before the
    next request is made."""
    out, seq = [], 0
    t0 = clock()
    while clock() < t0 + seconds:
        segment.poll(clock() - t0)
        with _annotate("client/make_rhs"):
            b = request_rhs(mix, seed, n, 0, seq)
        with _annotate("client/solve"):
            t_send = clock()
            rec = Sent(0, seq, b, t_send, t_send)
            # the answer is the client's once it is on the host: the
            # solve only dispatches, and collecting waits for it
            collect(rec, solve(b))
            rec.t_done = clock()
        out.append(rec)
        seq += 1
    segment.finish()
    return out
