"""Set-up: process start to the start of the measured window, in seconds
(imports, device check, data, every compile or cache load, warm-up)."""


def read(run):
    return run.setup_s
