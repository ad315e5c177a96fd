"""Device time per step of the relayouts: the ops that
``pipeline_other_ms`` counts (no stage scope, no collective) whose
innermost ``pin``/``relayout``/``comm.<strategy>`` scope is ``relayout``
(the edge adapters) or ``comm.<strategy>`` (the switch's permute, crop and
pad), mean over the chips.  A fused op counts by its root's scope."""
import spanreduce


def read(run):
    return spanreduce.pipeline_ms(run.trace, spanreduce.is_relayout)
