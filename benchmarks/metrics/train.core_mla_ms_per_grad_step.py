"""Device self time of the train module's ``dv3/core/mla`` operations in the window pass (latent projections, scores, output projection), forward and backward, per gradient step."""

from benchmarks import seq_scopes


def read(run):
    return seq_scopes.part_ms_per_grad_step(run, "core/mla")
