"""Share of the window spent inside ``server.step()`` outside the program's
admit, lookup_stall and dense spans (%): the controller, the cache plan,
the heat tracker and per-request bookkeeping, which no span covers."""
from chipbench.harness import step_untraced_share


def read(w):
    return step_untraced_share(w)
