"""Right-hand sides solved to the cell's tolerance and returned to
their client, over the time from the window's start until the last
answer due in the window came back."""


def read(run):
    conv = run.converged
    if not conv:
        return None
    return len(conv) / (max(r.t_done for r in run.done) - run.window_t0)
