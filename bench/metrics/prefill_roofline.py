"""Model prefill (``ServeEngine._prefill``): the least time the traced
prompts need, the larger of their operations over the chip's bf16 peak and
their bytes over its HBM bandwidth (``bench/families/<family>.py``
``prefill_flops`` and ``prefill_bytes``), as a share of the ``_prefill``
programs' device time, in percent."""

from bench.harness.metric import roofline_share


def read(run):
    tr = run.trace
    if tr is None:
        return None
    fam, c, pk = run.family, run.c, run.peaks
    n, busy = tr.program("_prefill")
    need_s = [
        max(fam.prefill_flops(c, s) / pk["bf16_flops_per_s"],
            fam.prefill_bytes(c, s) / pk["hbm_bytes_per_s"])
        for t, s in run.obs.admissions() if tr.inside(t)
    ]
    return roofline_share(n, busy, need_s)
