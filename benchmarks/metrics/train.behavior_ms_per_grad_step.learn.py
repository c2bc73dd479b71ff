"""Device self time of the train module's ``dv3/behavior`` operations (lambda returns, actor and critic losses), forward and backward, per gradient step."""

from benchmarks import scopes


def read(run):
    return scopes.part_ms_per_grad_step(run, "behavior")
