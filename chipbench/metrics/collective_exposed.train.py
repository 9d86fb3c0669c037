"""Share of the traced window in which a collective ran on a chip and no
other operation did (``trace_reduce.Reduced.exposed_collective_s``),
averaged over the cell's chips: the all-gathers of the sequence-split
residual stream, the REDUCE layers' exchange and the waits on
asynchronous permutes, where compute does not hide them.  The driver
hands over the trace's leaf operations only (``train_sharded.leaf_ops``):
a layer scan's ``while`` event spans its whole body and would count
every collective inside it as hidden."""
from chipbench import readers


def read(ctx):
    if not readers.traced(ctx) or ctx["window_s"] <= 0:
        return None
    red = ctx["reduced"]
    if not any(o.is_collective for o in red.ops):
        return None
    return 100.0 * red.exposed_collective_s() / ctx["window_s"]
