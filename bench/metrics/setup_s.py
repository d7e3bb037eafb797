"""Process start until the window opens: start-up, graph, cold start
and warm-up."""


def read(run):
    return run.setup_s or None
