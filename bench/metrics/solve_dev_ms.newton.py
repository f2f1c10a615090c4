"""Device busy milliseconds per Newton step inside the ``bench.solve`` span:
the fused triangular solves, residuals and refinement sweeps of
``solve(b, refine=3)``."""


def read(ctx):
    t = ctx.get("trace")
    n = t and t["span_count"].get("bench.solve")
    if ctx.get("kind") != "newton" or not n:
        return None
    return 1e3 * t["span_device_s"]["bench.solve"] / n
