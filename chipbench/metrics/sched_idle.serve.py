"""Device idle caused by the serving loop's host work, over the traced
window, in percent: time with no op on device 0, on the host's clock after
the skew is applied, that falls inside a ``serve.*`` span other than
``serve.wait`` (``serve.sweep``, ``serve.tables``, ``serve.dispatch``,
``serve.advance``, and ``serve.iter`` or ``serve.decode`` between them), or
inside a ``serve.wait`` after the device's last op there (the host learning
that the step is done and reading its tokens back).  Read from the
program's spans in the profiler's host plane (``spans.py``); the reader
wraps ``trace_reduce.reduce_file`` to read them when it is loaded."""
from chipbench import spans

spans.keep_program_spans()


def read(ctx):
    return spans.sched_idle_pct(ctx["reduced"], ctx["window_s"])
