"""Host staging: seconds the ParallelSolver constructor took (layout and
static stage, built on the host), by the host clock inside set-up."""


def read(ctx):
    return ctx["counters"].get("staging_s")
