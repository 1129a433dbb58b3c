from repro.kernels.decode_attention.ops import gqa_decode_paged
from repro.kernels.decode_attention.kernel import paged_decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
