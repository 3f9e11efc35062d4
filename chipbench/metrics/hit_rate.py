"""Cache tier: hits over probed (id, slot) pairs in the window (%)."""


def read(w):
    n = w.delta("lookups")
    return 100.0 * w.delta("hits") / n if n > 0 else None
