from repro.kernels.moe.kernel import expert_ffn_pallas
from repro.kernels.moe.ops import expert_ffn
from repro.kernels.moe.ref import expert_ffn_ref

__all__ = ["expert_ffn", "expert_ffn_pallas", "expert_ffn_ref"]
