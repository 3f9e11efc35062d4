"""Whole step on the chip: dense-stage operations per request (from the
configuration's shapes) times requests served per second, over the chips'
bf16 peak (%)."""
from chipbench.model import dense_flops_per_request


def read(w):
    rps = w.end_to_end.get("served_rps")
    if not rps or not w.peak:
        return None
    flops = dense_flops_per_request(w.config) * rps
    return 100.0 * flops / (w.chips * w.peak["bf16_flops_per_s"])
