"""Batcher: the serving thread's ``poll`` span (dequeue and the batcher's
``max_wait_s`` block, empty polls included), mean per poll that starts in
the window (ms)."""
from chipbench.spans import mean_span_ms


def read(w):
    return mean_span_ms(w, "poll")
