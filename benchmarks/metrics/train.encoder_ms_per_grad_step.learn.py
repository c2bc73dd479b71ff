"""Device self time of the train module's ``dv3/encoder`` operations (the encoder and the hoisted embedding projection), forward and backward, per gradient step."""

from benchmarks import scopes


def read(run):
    return scopes.part_ms_per_grad_step(run, "encoder")
