"""Serving-loop bookkeeping: the ``account`` span (attribution, latency
histogram, SLO feed, brownout flags and admission after the dense stage),
mean per batch whose span starts in the window (ms)."""
from chipbench.spans import mean_span_ms


def read(w):
    return mean_span_ms(w, "account")
