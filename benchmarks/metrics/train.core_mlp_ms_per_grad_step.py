"""Device self time of the train module's ``dv3/core/mlp`` operations in the window pass (the leading dense layer's SwiGLU MLP), forward and backward, per gradient step."""

from benchmarks import seq_scopes


def read(run):
    return seq_scopes.part_ms_per_grad_step(run, "core/mlp")
