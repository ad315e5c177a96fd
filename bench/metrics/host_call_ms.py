"""Mean host time per step from the ``get_solver`` call to ``solve``
returning, before the wait: plan-cache lookup, the solve's host path and
the enqueue of its device programs."""


def read(run):
    xs = run.host_call_s
    return sum(xs) / len(xs) * 1e3 if xs else None
