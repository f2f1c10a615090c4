"""Device milliseconds per sweep call inside the ``glu_factorize`` program
(``program_trace.reduce``'s ``program_device_s``), mean over the devices
used: the batched fused factorize."""


def read(ctx):
    p = ctx.get("program")
    if ctx.get("kind") != "sweep" or not p or not ctx.get("steps"):
        return None
    s = p["program_device_s"].get("glu_factorize")
    return None if s is None else 1e3 * s / ctx["steps"]
