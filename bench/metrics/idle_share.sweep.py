"""Share of the traced window in which no operation ran on the device, in
percent (mean over the devices used; each one's is on an earlier line of
standard error)."""


def read(ctx):
    t = ctx.get("trace")
    if ctx.get("kind") != "sweep" or not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
