"""What the serving thread's spans say about the window, for the per-layer
readers: the mean of one span, and the time inside ``server.step()`` that
no span covers.  Reads only ``Window.spans``, ``marks`` and
``drive.steps``; a span the program does not emit reads as nothing."""
from __future__ import annotations

from chipbench import devtrace


def mean_span_ms(w, name: str) -> float | None:
    """Mean duration of the ``name`` spans that start in the window (ms)."""
    lo, hi = w.marks
    durs = [e - s for n, s, e in w.spans if n == name and lo <= s < hi]
    return 1e3 * sum(durs) / len(durs) if durs else None


def step_residual_share(w) -> float | None:
    """Share of the window inside ``server.step()`` and outside the union
    of every serving-thread span (%): ``step_untraced_share`` with every
    span subtracted, not only admit, lookup_stall and dense (the serving
    thread opens spans only inside ``step()``)."""
    lo, hi = w.marks
    steps = devtrace.clip(map(tuple, w.drive.steps), lo, hi)
    if not steps or not w.spans:
        return None
    traced = devtrace.merge(devtrace.clip([(s, e) for _, s, e in w.spans],
                                          lo, hi))
    in_step = sum(e - s for s, e in steps)
    return 100.0 * (in_step - sum(e - s for s, e in traced)) / (hi - lo)
