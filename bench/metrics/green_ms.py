"""Device time per step of the ops under the ``green`` scope (the
pointwise Green multiply), mean over the chips."""
import reduce


def read(run):
    return reduce.per_step_ms(run.trace, reduce.in_stage(("green",)))
