"""Device self time of the train module's ``dv3/imagination`` operations (the horizon-step imagination scan), forward and backward, per gradient step."""

from benchmarks import scopes


def read(run):
    return scopes.part_ms_per_grad_step(run, "imagination")
