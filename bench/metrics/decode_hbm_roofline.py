"""Model decode step (``SlotScheduler._step``): the bytes the traced steps
need, over the chip's HBM bandwidth, as a share of the ``_step`` program's
device time, in percent. A step needs the weights once and each active
slot's state (``bench/families/<family>.py`` ``step_bytes``); it does not
matter what the program happens to read besides."""

from bench.harness.metric import roofline_share


def read(run):
    tr = run.trace
    if tr is None:
        return None
    n, busy = tr.program("_step")
    bw = run.peaks["hbm_bytes_per_s"]
    need_s = [run.family.step_bytes(run.c, pos) / bw
              for t, pos in run.obs.steps() if tr.inside(t)]
    return roofline_share(n, busy, need_s)
