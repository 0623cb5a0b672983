from . import ops
from .decode_attention import block_len, decode_attention_kernel
from .ops import block_of, decode_attention
from .ref import decode_attention_ref

__all__ = ["ops", "block_len", "block_of", "decode_attention",
           "decode_attention_kernel", "decode_attention_ref"]
