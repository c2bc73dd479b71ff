"""Device self time of the train module's ``dv3/optimizer`` operations (the three optimizer updates, the target EMA and the gradient norms), forward and backward, per gradient step."""

from benchmarks import scopes


def read(run):
    return scopes.part_ms_per_grad_step(run, "optimizer")
