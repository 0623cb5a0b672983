"""Routed experts (``models/moe.py``, ``kernels/moe_gmm``): the least time
the grouped matmuls of the traced prefills and steps need, the larger of
their operations over the chip's bf16 peak and their bytes over its HBM
bandwidth, as a share of the device time that the kernel's operations and
the staging of their weights took in the traced slice, in percent.

The operations and bytes come from the program's counters, which the
``serve.step`` and ``serve.admit.prefill`` spans carry (``moe_rows``, the
(token, held expert) pairs summed over layers; ``moe_experts_hit``, the
(layer, held expert) pairs hit): 6 rows d ff operations, and each hit
expert's weights once plus each row in and out of each call
(``bench/families/moe.py`` ``gmm_flops``, ``gmm_bytes``).

The device time is the union of two kinds of operation in the chip's
trace. The kernel's custom calls are named ``%moe_gmm.<n>`` (the Pallas
kernel's name, ``kernels/moe_gmm/moe_gmm.py`` ``NAME``). The layer loop
slices one layer's held experts out of the stacked weights before the
kernel reads them, and on a v5e XLA puts that slice in VMEM: the trace
names it ``%dynamic-slice_bitcast_fusion.<n> = bf16[16,2048,1024]...S(1)``
(and ``bf16[16,1024,2048]`` for the down projection). The kernel then
reads VMEM, and the weights' trip from HBM is the slice's time, so the
reader counts every operation whose result is one layer's held experts'
matrix (E_h x d x ff or E_h x ff x d, in the weights' dtype) with the
kernel. A program without the counters or the kernel reads nothing."""

import re

KERNEL = re.compile(r"^%?moe_gmm(\.\d+)?\s")
SPANS = ("serve.step", "serve.admit.prefill")
DTYPES = {"bfloat16": "bf16", "float32": "f32", "float16": "f16"}


def _weights(c: dict, s: dict):
    """Operations whose result is one layer's held experts' matrix."""
    dt = DTYPES.get(c["dtype"], c["dtype"])
    dims = "|".join(f"{s['held']},{a},{b}" for a, b in
                    ((s["d"], s["ff"]), (s["ff"], s["d"])))
    return re.compile(rf"^%\S+ = {dt}\[(?:{dims})\]")


def read(run):
    tr = run.trace
    if tr is None or tr.devices == 0 or tr.window_s <= 0:
        return None
    try:
        from repro.core import spans
    except ImportError:
        return None
    fam, c, pk = run.family, run.c, run.peaks
    if not hasattr(fam, "gmm_flops"):
        return None
    need = 0.0
    for r in spans.records():
        a = r.attrs or {}
        if r.name in SPANS and "moe_rows" in a and tr.inside(r.t0_ns / 1e9):
            need += max(
                fam.gmm_flops(c, a["moe_rows"]) / pk["bf16_flops_per_s"],
                fam.gmm_bytes(c, a["moe_rows"], a["moe_experts_hit"])
                / pk["hbm_bytes_per_s"])
    weights = _weights(c, fam.sizes(c))
    ops = sorted((max(a, tr.lo), min(b, tr.hi)) for a, b, n in tr.ops
                 if (KERNEL.match(n) or weights.match(n))
                 and b > tr.lo and a < tr.hi)
    busy, end = 0, None
    for a, b in ops:                      # the union: no time counted twice
        if end is not None and a < end:
            a = end
        if b > a:
            busy += b - a
            end = b
    if need <= 0 or busy <= 0:
        return None
    return 100.0 * need / (busy / 1e9)
