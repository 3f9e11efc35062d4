"""``step_residual_share`` in a cell above the knee, where it moves
``served_rps``."""
from chipbench.spans import step_residual_share


def read(w):
    return step_residual_share(w)
