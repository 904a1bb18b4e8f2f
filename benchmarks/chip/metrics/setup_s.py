"""Set-up: process start to the opening of the measured window (build,
compile or cache load, warm-up, prefix fill, the load's preroll)."""


def read(run):
    return run.t_window - run.t_process
