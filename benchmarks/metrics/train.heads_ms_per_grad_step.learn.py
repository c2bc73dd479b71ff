"""Device self time of the train module's ``dv3/heads`` operations (prior logits, decoder, reward and continue heads and the reconstruction loss), forward and backward, per gradient step."""

from benchmarks import scopes


def read(run):
    return scopes.part_ms_per_grad_step(run, "heads")
