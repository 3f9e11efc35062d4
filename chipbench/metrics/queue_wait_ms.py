"""Batcher: arrival to admit, mean per request retired in the window (ms),
from the program's ``serve.queue_wait`` histogram."""


def read(w):
    return w.mean_ms("queue_wait")
