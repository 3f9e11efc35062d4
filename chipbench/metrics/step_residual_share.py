"""Share of the window inside ``server.step()`` outside every serving-thread
span (%): the step's work that no span names."""
from chipbench.spans import step_residual_share


def read(w):
    return step_residual_share(w)
