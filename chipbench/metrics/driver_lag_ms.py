"""How late the driver submitted: submit instant minus intended arrival,
mean over the requests that arrived in the window (ms)."""
import numpy as np


def read(w):
    lag = (w.drive.submit - w.drive.arrival)[w.in_window]
    lag = lag[np.isfinite(lag)]
    return 1e3 * float(lag.mean()) if len(lag) else None
