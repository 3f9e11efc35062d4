"""Controller: the ``heat`` span (load monitor and the frequency tracker's
per-batch merge), mean per batch whose span starts in the window (ms)."""
from chipbench.spans import mean_span_ms


def read(w):
    return mean_span_ms(w, "heat")
