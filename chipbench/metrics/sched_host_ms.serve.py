"""Host time of the serving loop per decode iteration: the median, over the
traced window's ``serve.iter`` spans that hold a ``serve.wait``, of the
iteration's duration less its ``serve.wait`` time (the blocking read of the
sampled tokens).  It is the host's own time a step, not the device's idle:
part of it (the dispatch of the sampling programs) overlaps the step's
compute, and only the rest idles the device and adds to the inter-token gap
of the synchronous loop (``sched_idle.serve`` reads that part).  Once the
loop runs ahead of the device, it is the least time a step can take.  Read
from the program's spans in the profiler's host plane (``spans.py``); the
reader wraps ``trace_reduce.reduce_file`` to read them when it is loaded."""
from chipbench import spans

spans.keep_program_spans()


def read(ctx):
    return spans.sched_host_ms(ctx["reduced"])
