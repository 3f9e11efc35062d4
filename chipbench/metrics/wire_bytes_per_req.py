"""Miss engine: wire bytes moved (responses and requests) per request
retired in the window (B), from the tier's byte counters."""


def read(w):
    n = w.delta("requests")
    if n <= 0:
        return None
    return (w.delta("bytes_network") + w.delta("bytes_request")) / n
