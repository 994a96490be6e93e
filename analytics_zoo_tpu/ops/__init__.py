"""Hand-written TPU kernels (Pallas) for the hot ops.

Reference parity note (SURVEY.md §2.10): the reference's native kernel layer
was Intel MKL/MKL-DNN behind BigDL's JNI `Engine`.  The TPU-native equivalent
is (a) XLA's own fusions for almost everything, plus (b) the Pallas kernels in
this package for the few ops where a hand schedule beats XLA — today that is
flash attention (O(T) memory softmax-attention, MXU-tiled), the chunked
gated delta rule (``gated_delta_rule``: the linear-attention recurrence with
a chunk's terms and the state in VMEM, forward and backward; called through
``nn.linear_attention.gated_delta_rule``), the ways into it
(``gdn_qkv_conv``) and out of it (``gdn_gated_norm``) and the chunked
Mamba-2 scan (``mamba2_ssd``: a chunk's
``[Q, Q]`` terms and the carried state in VMEM, forward and backward;
called through ``nn.state_space.ssd``).
"""

from .flash_attention import flash_attention, mha_reference
from .fused_xent import fused_softmax_xent, fused_softmax_xent_tokens

__all__ = ["flash_attention", "mha_reference", "fused_softmax_xent",
           "fused_softmax_xent_tokens"]
