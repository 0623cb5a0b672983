"""Host to device lift (``ServeEngine._lift_params``): the wall time of
``ServeEngine.from_workspace`` until the params are on the device, less the
epoch load's ``startup_s``, in seconds."""


def read(run):
    return run.load["lift_s"]
