"""Open-loop driver on the calling thread.

Submits each request at its intended arrival time through
``FlexEMRServer.submit`` (stamped with that time, so a late submission is
charged to the request) and steps the server in between.  Completion never
feeds back into submission.  Unlike ``repro.loadgen.OpenLoopDriver`` it
keeps every retired score: the server retires in submission order (FIFO
batcher, FIFO pipeline), so the k-th retired score is the k-th request's.
It also records each ``step()`` call's interval and calls ``on_mark`` once
when the window opens and once when it closes.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np


@dataclasses.dataclass
class Drive:
    arrival: np.ndarray  # intended arrival, perf_counter seconds
    submit: np.ndarray  # submit instant (nan: never submitted)
    retire: np.ndarray  # retire instant (nan: never retired)
    scores: np.ndarray  # served score (nan: never retired)
    steps: np.ndarray  # [m, 2] perf_counter start/end of each step()
    error: str | None


def drive(server, payloads: list[dict], t_rel: np.ndarray, epoch: float,
          need: int, marks: tuple[float, float], on_mark, give_up: float,
          idle_sleep: float = 0.0005) -> Drive:
    """Run until the first ``need`` requests have retired, or until
    ``give_up`` (perf_counter) passes, or a step raises.

    ``marks`` are the window's open and close (perf_counter); ``on_mark(i,
    now)`` is called at the first loop turn at or past each.
    """
    n = len(payloads)
    arrival = epoch + np.asarray(t_rel, np.float64)
    submit = np.full(n, np.nan)
    retire = np.full(n, np.nan)
    scores = np.full(n, np.nan)
    steps: list[tuple[float, float]] = []
    error = None
    i = retired = 0
    mark = 0
    while retired < need:
        now = time.perf_counter()
        if mark < len(marks) and now >= marks[mark]:
            on_mark(mark, now)
            mark += 1
            now = time.perf_counter()
        if now > give_up:
            break
        while i < n and arrival[i] <= now:
            server.submit(payloads[i], arrival=float(arrival[i]))
            submit[i] = now
            i += 1
        s0 = time.perf_counter()
        try:
            out = server.step()
        except Exception as exc:  # noqa: BLE001 - reported as failed requests
            error = f"{type(exc).__name__}: {exc}"
            break
        s1 = time.perf_counter()
        steps.append((s0, s1))
        if out is not None:
            k = len(out["degraded"])  # requests in the batch, padding excluded
            scores[retired:retired + k] = np.asarray(out["scores"])[:k]
            retire[retired:retired + k] = s1
            retired += k
        elif i < n:
            wait = arrival[i] - time.perf_counter()
            if wait > 0:
                time.sleep(min(wait, idle_sleep))
    while mark < len(marks) and error is None:
        # The window closed during the drain: mark it when its time comes.
        now = time.perf_counter()
        if now < marks[mark]:
            time.sleep(marks[mark] - now)
            now = time.perf_counter()
        on_mark(mark, now)
        mark += 1
    return Drive(arrival, submit, retire, scores,
                 np.asarray(steps, np.float64).reshape(-1, 2), error)
