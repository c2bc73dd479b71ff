"""Device self time of the train module's ``dv3/core/gdn`` operations in the window pass, forward and backward, per gradient step."""

from benchmarks import seq_scopes


def read(run):
    return seq_scopes.part_ms_per_grad_step(run, "core/gdn")
