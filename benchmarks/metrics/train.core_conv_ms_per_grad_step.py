"""Device self time of the train module's ``dv3/core/conv`` operations in the window pass (the in-projection, the two gates and the convolution, the out-projection), forward and backward, per gradient step."""

from benchmarks import seq_scopes


def read(run):
    return seq_scopes.part_ms_per_grad_step(run, "core/conv")
