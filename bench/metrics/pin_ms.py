"""Device time per step of the row-major pins: the ops that
``pipeline_other_ms`` counts (no stage scope, no collective) whose
innermost ``pin``/``relayout``/``comm.<strategy>`` scope is ``pin``, mean
over the chips.  A fused op counts by its root's scope."""
import spanreduce


def read(run):
    return spanreduce.pipeline_ms(run.trace, spanreduce.is_pin)
