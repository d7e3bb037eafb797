"""Wall seconds of the program's ``construct/eliminate`` spans (the
wavefront rounds, until their round count is on the host) inside the
traced cold start's ``cold_start/factor`` span."""
from bench import scopes


def read(run):
    got = scopes.construction(run)
    return None if got is None else got.get("construct/eliminate")
