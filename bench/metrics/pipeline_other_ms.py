"""Device busy time per step outside every stage scope and every
collective: pads, crops, relayouts, copies and the benchmark's own
right-hand-side scaling, mean over the chips."""
import reduce


def read(run):
    return reduce.per_step_ms(run.trace, lambda ops: reduce.stage_time(
        ops, lambda o: o.stage is None and not o.collective))
