"""Host seconds the planner took to build the cell's plan
(``SymbolicPlan.build_seconds["total"]``): ordering, MC64, symbolic
fill-in, levelization and the factorization plan."""


def read(ctx):
    return ctx.get("plan_build_s")
