"""Decode attention (``kernels/decode_attention``, called by the model
decode step): the least time the traced steps' attention needs, its bytes
over the chip's HBM bandwidth, as a share of the device time that the
kernel's calls took in the traced slice, in percent.

The bytes come from the counters that the ``serve.step`` spans carry
(worked out on the host from the active rows' positions):
``attn_kv_read``, the positions the kernel is handed, summed over active
rows and layers and rounded up to its block, times the keys and values of
one position of one layer (2 KV hd, in the cache's dtype); and each active
row's query and output in each layer (2 H hd). The attention's operations
(4 H hd a position) are under a tenth of the bf16 peak over that time in
every cell, so bytes bound it.

The device time is the union of the operations that the chip's trace names
``%decode_attention.<n>``, the Pallas kernel's name
(``kernels/decode_attention/decode_attention.py`` ``NAME``). A program
without the counters or the kernel reads nothing."""

import re

from bench.harness.trace import Trace

KERNEL = re.compile(r"^%?decode_attention(\.\d+)?\s")


def read(run):
    tr = run.trace
    if tr is None or tr.devices == 0 or tr.window_s <= 0:
        return None
    try:
        import jax.numpy as jnp
        from repro.core import spans
    except ImportError:
        return None
    s = run.family.sizes(run.c)
    item = jnp.dtype(run.c["dtype"]).itemsize
    position = 2 * s["kv"] * s["hd"] * item      # K and V, one layer
    row = 2 * s["heads"] * s["hd"] * item        # a query and its output
    need = 0
    for r in spans.records():
        a = r.attrs or {}
        if r.name == "serve.step" and "attn_kv_read" in a \
                and tr.inside(r.t0_ns / 1e9):
            need += (a["attn_kv_read"] * position
                     + a["n_active"] * s["layers"] * row)
    kernel = Trace(ops=[e for e in tr.ops if KERNEL.match(e[2])],
                   lo=tr.lo, hi=tr.hi)
    busy_s = kernel.busy_s()              # the union: no time counted twice
    if need <= 0 or busy_s <= 0:
        return None
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / busy_s
