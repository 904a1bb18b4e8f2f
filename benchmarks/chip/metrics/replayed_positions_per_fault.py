"""Engine continuation: positions the fault commits inside the window put
back to replay, per commit: each suspended request's prompt plus the
tokens it had generated."""


def read(run):
    faults = [f for f in run.faults
              if run.t_window <= f.t_commit < run.t_window_end]
    if not faults:
        return None
    return sum(f.replay_positions for f in faults) / len(faults)
