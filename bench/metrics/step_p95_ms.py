"""95th percentile (nearest rank) of the per-step time, from the step's
call until its solution is ready, over every step of the window."""
from harness import percentile


def read(run):
    if len(run.step_s) < 20:
        return None
    return percentile(run.step_s, 95) * 1e3
