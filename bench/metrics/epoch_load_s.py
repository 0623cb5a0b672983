"""Linker epoch load (``Workspace.load``, ``core/executor.py``): the load's
own ``startup_s`` at set-up, in seconds."""


def read(run):
    return run.load["epoch_load_s"]
