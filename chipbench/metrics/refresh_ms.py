"""Controller: the ``refresh`` span (one cache-plan application: plan, row
fetch, insert, shard affinity), mean per refresh that starts in the window
(ms)."""
from chipbench.spans import mean_span_ms


def read(w):
    return mean_span_ms(w, "refresh")
