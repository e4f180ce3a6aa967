from repro.kernels.paged_attention.ops import (
    PagedInfo,
    paged_attention,
    paged_latent_attention,
    paged_prefill,
)
from repro.kernels.paged_attention.kernel import paged_attention_pallas
from repro.kernels.paged_attention.latent_kernel import (
    paged_latent_attention_pallas,
)
from repro.kernels.paged_attention.prefill_kernel import paged_prefill_pallas
from repro.kernels.paged_attention.ref import (
    paged_attention_ref,
    paged_latent_attention_ref,
    paged_prefill_ref,
)

__all__ = [
    "PagedInfo",
    "paged_attention",
    "paged_attention_pallas",
    "paged_attention_ref",
    "paged_latent_attention",
    "paged_latent_attention_pallas",
    "paged_latent_attention_ref",
    "paged_prefill",
    "paged_prefill_pallas",
    "paged_prefill_ref",
]
