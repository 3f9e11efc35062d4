"""``step_untraced_share`` in a cell above the knee, where it moves
``served_rps``."""
from chipbench.harness import step_untraced_share


def read(w):
    return step_untraced_share(w)
