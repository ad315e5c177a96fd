"""Host clock around the first ``get_solver``: plan, Green's function,
device placement of the plan's constants."""


def read(run):
    return run.plan_build_s
