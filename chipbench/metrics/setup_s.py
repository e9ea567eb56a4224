"""Process start to the first measured step or request, compile (or load
from the persistent cache) included.  Host wall clock.  End to end."""


def read(run):
    return run["setup_s"]
