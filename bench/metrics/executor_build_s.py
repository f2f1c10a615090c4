"""Host seconds ``GLU`` took to build its factorizer and triangular solver
and move the plan's arrays to the device (``GLU.setup_seconds["executor"]``,
the span ``glu.executor_build``)."""


def read(ctx):
    return (ctx.get("setup_seconds") or {}).get("executor")
