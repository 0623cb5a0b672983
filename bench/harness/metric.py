"""Arithmetic shared by the per-layer metrics' readers."""

from __future__ import annotations


def roofline_share(n_programs: int, device_s: float, need_s: list):
    """The least time the traced programs could take, as a share of the
    time they took, in percent: the mean least time of the host's records
    of them, times the number of programs the device ran. None where the
    trace or the host saw none."""
    if n_programs == 0 or device_s <= 0 or not need_s:
        return None
    return 100.0 * n_programs * (sum(need_s) / len(need_s)) / device_s


def model_flops_share(run):
    tr = run.trace
    if tr is None or tr.devices == 0 or tr.window_s <= 0:
        return None
    fam, c, o = run.family, run.c, run.obs
    flops = sum(fam.prefill_flops(c, s)
                for t, s in o.admissions() if tr.inside(t))
    flops += sum(fam.decode_flops(c, p)
                 for t, pos in o.steps() if tr.inside(t) for p in pos)
    if flops == 0:
        return None
    return 100.0 * flops / (tr.window_s * run.peaks["bf16_flops_per_s"])
