"""100 x (1 - union of the device's op intervals / traced window), for the
idlest chip."""
import reduce


def read(run):
    tr = run.trace
    w = reduce.window(tr) if tr is not None else None
    ops = reduce.device_ops(tr) if w else {}
    if not ops:
        return None
    span = w[1] - w[0]
    return max(100.0 * (1.0 - reduce.length(reduce.union(
        (o.start, o.end) for o in dev)) / span) for dev in ops.values())
