"""Share of the column-wise kernels' roofline in the sharded train step:
the least time of every execution of a Pallas kernel tagged
``kernel_metadata={"kernel":"colwise_nm"}`` (kept MACs; bytes of ``x``,
``values``, ``idx`` and ``y``, from the operand shapes in its HLO text,
``work_train.kernel_ops``) over the device time of those executions.
Each forward a step runs counts, the recompute of remat among them: it is
the kernel's own efficiency, where ``mfu.train`` counts the step's work
once.  A tagged kernel whose operands are not ``x``, indices and
``values`` fails the run, since its work could not be counted."""
import re

from chipbench import readers, work_train

TAG = re.compile(r'kernel_metadata=\{\s*"kernel":"colwise_nm"\s*\}')


def claims(op) -> bool:
    return op.is_pallas and bool(TAG.search(op.text))


def read(ctx):
    if not readers.traced(ctx):
        return None
    least = spent = 0.0
    for o in ctx["reduced"].ops:
        if not claims(o):
            continue
        ops = work_train.kernel_ops(o.text)
        if ops is None:
            raise ValueError(f"column-wise kernel {o.name} with operands the "
                             f"reader cannot count: {o.text[:300]}")
        least += readers.least_time_s([ops], ctx["device_kind"])
        spent += o.dur_ns * 1e-9
    return readers.share_pct(least, spent)
