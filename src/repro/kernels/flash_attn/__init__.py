from repro.kernels.flash_attn.kernel import flash_attention_pallas  # noqa: F401
from repro.kernels.flash_attn.ops import flash_attention  # noqa: F401
from repro.kernels.flash_attn.paged import (  # noqa: F401
    paged_attention,
    paged_attention_pallas,
    paged_attention_ref,
    paged_vmem_bytes,
)
from repro.kernels.flash_attn.ref import flash_attention_ref  # noqa: F401
