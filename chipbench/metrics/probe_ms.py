"""The ``serve.attr.probe`` stage, mean per batch retired in the window (ms)."""


def read(w):
    return w.mean_ms("attr.probe")
