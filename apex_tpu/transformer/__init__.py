"""apex_tpu.transformer — attention, transformer blocks, and
sequence/context parallelism (ring attention over the mesh).

New capability relative to the 2019 reference (which has no attention,
SURVEY.md §5): long-context support is first-class in apex_tpu.
"""

from .attention import (dot_product_attention,
                        dot_product_attention_token_major,
                        MultiheadAttention)
from .short_conv import GatedShortConv, gated_short_conv
from .mamba2 import Mamba2Mixer, ssd_chunked
from .mla import LatentAttention, rope_interleaved
from .ring_attention import ring_attention, ring_self_attention
from .ulysses import ulysses_attention, ulysses_self_attention
