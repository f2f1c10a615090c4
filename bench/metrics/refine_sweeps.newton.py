"""Mean refinement sweeps per Newton step (``solve_info["refine_iters"]``)."""


def read(ctx):
    iters = ctx.get("refine_iters")
    if ctx.get("kind") != "newton" or not iters:
        return None
    return sum(iters) / len(iters)
