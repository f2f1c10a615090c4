"""Device busy milliseconds per ``refactorize_solve`` call inside the
``bench.sweep`` span (mean over the devices used)."""


def read(ctx):
    t = ctx.get("trace")
    n = t and t["span_count"].get("bench.sweep")
    if ctx.get("kind") != "sweep" or not n:
        return None
    return 1e3 * t["span_device_s"]["bench.sweep"] / n
