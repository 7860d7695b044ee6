from repro.kernels.msa.msa_fused import (WL_FIELDS, block_pages,
                                        build_worklist, pad_worklist)
from repro.kernels.msa.ops import (
    apply_page_copies,
    apply_swap_ins,
    msa_fused,
    msa_fused_partial,
    write_kv_pages,
)

__all__ = ["apply_page_copies", "apply_swap_ins", "block_pages",
           "build_worklist", "msa_fused", "msa_fused_partial",
           "pad_worklist", "write_kv_pages", "WL_FIELDS"]
