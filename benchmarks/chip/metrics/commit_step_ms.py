"""Membership control plane: mean wall time of the frontend steps that
committed a rank fault inside the window (detection verdict, repair plan,
slot moves, validation, publication, and that step's forward pass)."""


def read(run):
    steps = [f.step_s for f in run.faults
             if run.t_window <= f.t_commit < run.t_window_end]
    return 1e3 * sum(steps) / len(steps) if steps else None
