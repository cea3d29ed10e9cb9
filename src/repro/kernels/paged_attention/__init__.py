from repro.kernels.paged_attention.kernel import paged_decode_attention
from repro.kernels.paged_attention.ops import paged_attention, supports
