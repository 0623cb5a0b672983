from . import ops
from .moe_gmm import moe_gmm
from .ops import gmm
from .ref import moe_gmm_ref

__all__ = ["ops", "gmm", "moe_gmm", "moe_gmm_ref"]
