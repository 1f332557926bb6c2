from repro.kernels.paged_attention.ops import (chunked_prefill_attention,
                                               mla_paged_attention)
from repro.kernels.paged_attention.ref import (chunked_prefill_reference,
                                               mla_paged_reference,
                                               paged_attention_reference)

__all__ = ["paged_attention_reference",
           "chunked_prefill_attention", "chunked_prefill_reference",
           "mla_paged_attention", "mla_paged_reference"]
