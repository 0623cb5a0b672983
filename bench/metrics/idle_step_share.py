"""Per-step host turn-around (``SlotScheduler.step``, ``pop_finished``): the
device's idle time inside the program's ``serve.step`` spans (dispatch,
token sync, streaming, retirement), as a share of the traced slice, in
percent."""

from bench.harness.spans import idle_share_in


def read(run):
    return idle_share_in(run.trace, "serve.step")
