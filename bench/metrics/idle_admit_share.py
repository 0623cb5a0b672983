"""Slot admission (``SlotScheduler.admit``): the device's idle time inside
the program's ``serve.admit`` spans (prefill dispatch, first-token pick,
splice, first-token sync), as a share of the traced slice, in percent."""

from bench.harness.spans import idle_share_in


def read(run):
    return idle_share_in(run.trace, "serve.admit")
