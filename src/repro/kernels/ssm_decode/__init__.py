from . import ops
from .ops import ssm_decode
from .ref import ssm_decode_ref
from .ssm_decode import chunk_heads, ssm_decode_kernel

__all__ = ["ops", "chunk_heads", "ssm_decode", "ssm_decode_kernel",
           "ssm_decode_ref"]
