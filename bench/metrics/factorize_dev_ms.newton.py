"""Device busy milliseconds per Newton step inside the ``bench.factorize``
span: the fused factorize program, waited for inside its span."""


def read(ctx):
    t = ctx.get("trace")
    n = t and t["span_count"].get("bench.factorize")
    if ctx.get("kind") != "newton" or not n:
        return None
    return 1e3 * t["span_device_s"]["bench.factorize"] / n
