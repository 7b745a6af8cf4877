"""How evenly the router spread the window's searches over the replicas:
N x the fewest searches any replica served over the searches all served,
from the router's own ``n_served`` counters (``GET /v1/replicas``) read at
the window's start and end.  1.0 is even; None where no router fronts the
cell."""


def read(ctx):
    served = ctx.served
    if not served or not sum(served):
        return None
    return len(served) * min(served) / sum(served)
