"""Shared Pallas-TPU helpers for the kernel modules.

The manual-DMA surface (``make_async_copy``, DMA semaphores, the HBM memory
space), the two-slot double-buffer protocol, the
f32-accumulating MXU contraction, and the one-hot gathers that stand in for
dynamic gathers Mosaic cannot lower.  The ``repro.analysis`` kernel lints key
off the names imported from this module.
"""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

COMPILER_PARAMS = pltpu.CompilerParams

# memory space of a pallas_call input that stays un-blocked in HBM, so the
# kernel can DMA windows of it manually.  HBM, not ANY: under ANY the
# compiler may place a small operand in VMEM, where a window narrower than
# the 128-lane tiling (e.g. a 64-wide head dim) is refused.
MEM_HBM = pltpu.HBM

# Kernel families.  Every ``pallas_call`` passes ``metadata=kernel_tag(f)``
# for its family ``f``; the compiled custom call carries it as
# ``frontend_attributes={kernel_metadata={"kernel": "<f>"}}`` in its HLO
# text, so a profiler trace names the kernel whatever the jitted wrapper
# around it is called (inside a scanned layer every kernel is a
# ``closed_call``).
KERNEL_FAMILIES = ("colwise_nm", "conv_fused", "conv_fused_banded",
                   "im2col_pack", "paged_attn", "flash_attn")


def kernel_tag(family: str) -> dict:
    """The ``pallas_call`` metadata naming ``family``."""
    if family not in KERNEL_FAMILIES:
        raise ValueError(f"unknown kernel family {family!r}; add it to "
                         f"KERNEL_FAMILIES")
    return {"kernel": family}


# Reduction-axis chunk of the one-hot gathers: bounds the [chunk, k] one-hot
# temporary a single gather keeps live in VMEM.
GATHER_CHUNK = 512


def make_async_copy(src_ref, dst_ref, sem_ref):
    """Async copy descriptor (``.start()`` / ``.wait()``) between memory
    spaces, shared by every double-buffered kernel.  Interpret mode executes
    the same descriptor (jax simulates the semaphore), so the DMA path is
    testable on CPU."""
    return pltpu.make_async_copy(src_ref, dst_ref, sem_ref)


class GuardedCopies:
    """Several async copies started and waited as one descriptor, each only
    where its predicate holds (the same predicate on both sides, so a wait
    never blocks on a copy that was not started): a descriptor for
    :func:`double_buffer_rotate` whose chunk is a variable number of
    windows, such as the pages of a ragged final block."""

    def __init__(self, copies):
        self.copies = list(copies)  # [(predicate, descriptor)]

    def start(self):
        for pred, copy in self.copies:
            pl.when(pred)(copy.start)

    def wait(self):
        for pred, copy in self.copies:
            pl.when(pred)(copy.wait)


def dma_semaphores(n: int):
    """Scratch-shape entry for ``n`` DMA completion semaphores."""
    return pltpu.SemaphoreType.DMA((n,))


def double_buffer_rotate(dma, g, n_chunks, *, gate):
    """THE two-slot DMA rotation protocol, shared by every double-buffered
    kernel (banded conv megakernel, pipelined strip GEMM) so the
    correctness-critical ordering lives in one place.

    Under ``gate`` (the predicate marking the first grid step of chunk
    ``g``): warm up chunk 0's copy, start the prefetch of chunk g+1 into the
    other slot, THEN block on chunk g — so chunk g+1 streams in behind chunk
    g's compute.  ``dma(slot, gi)`` must return the async-copy descriptor
    for chunk ``gi`` into scratch slot ``slot``; the descriptor a ``wait``
    reconstructs must be identical to the one ``start`` used.
    """

    @pl.when(gate)
    def _rotate():
        @pl.when(g == 0)
        def _warmup():
            dma(0, 0).start()

        @pl.when(g + 1 < n_chunks)
        def _prefetch():
            dma((g + 1) % 2, g + 1).start()

        dma(g % 2, g).wait()


def dot_f32(a, b, interpret: bool, *, trans_a: bool = False):
    """MXU dot with float32 accumulation, shared by every accumulate-flush
    kernel (``trans_a``: contract ``a``'s leading dim, i.e. ``a.T @ b``).
    Interpret mode casts the operands up first — XLA:CPU has no
    bf16xbf16->f32 dot, while the TPU path feeds the MXU native operands.
    float32 operands contract at full precision, so one-hot gathers of f32
    data stay exact on the chip."""
    if interpret:
        a = a.astype(jnp.float32)
        b = b.astype(jnp.float32)
    dims = (((0,) if trans_a else (a.ndim - 1,), (0,)), ((), ()))
    precision = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
                 else None)
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _onehot(ids, c0: int, c1: int, dtype):
    """[c1 - c0, k] one-hot: entry (r, j) is 1 where ``ids[0, j] == c0 + r``."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (c1 - c0, ids.shape[-1]), 0)
    return (rows + c0 == ids).astype(dtype)


def gather_cols(x_ref, ids, interpret: bool):
    """``x[:, ids]`` of a 2-D VMEM ref/array ``x`` [rows, n] for a lane
    vector ``ids`` [1, k] of column ids — as a one-hot MXU contraction, in
    ``GATHER_CHUNK`` slices of the n axis.  Mosaic lowers no general lane
    gather; the contraction is exact (each output sums one product with
    1.0).  Returns float32 [rows, k]."""
    n = x_ref.shape[1]
    out = None
    for c0 in range(0, n, GATHER_CHUNK):
        c1 = min(n, c0 + GATHER_CHUNK)
        xs = x_ref[:, c0:c1]
        part = dot_f32(xs, _onehot(ids, c0, c1, xs.dtype), interpret)
        out = part if out is None else out + part
    return out


def gather_rows(x_ref, ids, interpret: bool):
    """``x[ids, :]`` of a 2-D VMEM ref/array ``x`` [n, V] for a lane vector
    ``ids`` [1, k] of row ids — the sublane twin of :func:`gather_cols`
    (one-hot contracted over its leading dim).  Returns float32 [k, V]."""
    n = x_ref.shape[0]
    out = None
    for c0 in range(0, n, GATHER_CHUNK):
        c1 = min(n, c0 + GATHER_CHUNK)
        xs = x_ref[c0:c1, :]
        part = dot_f32(_onehot(ids, c0, c1, xs.dtype), xs, interpret,
                       trans_a=True)
        out = part if out is None else out + part
    return out


def gather_vmem_bytes(rows: int, k: int, in_bytes: int) -> int:
    """VMEM temporaries of one :func:`gather_cols`/:func:`gather_rows` call:
    the int32 compare and cast one-hot of one chunk, plus the f32 result."""
    return GATHER_CHUNK * k * (4 + in_bytes) + rows * k * 4


def should_interpret() -> bool:
    """One interpret-mode policy for every kernel wrapper: compiled Mosaic on
    TPU, interpret mode everywhere else."""
    return jax.default_backend() != "tpu"


def ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m
