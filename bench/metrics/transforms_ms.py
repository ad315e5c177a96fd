"""Device time per step of the ops under the 1-D transform scopes
``fwd.<d>`` and ``bwd.<d>`` (a fused ``fwd.<d>+green`` counts here), mean
over the chips."""
import reduce


def read(run):
    return reduce.per_step_ms(run.trace, reduce.in_stage(("fwd", "bwd")))
