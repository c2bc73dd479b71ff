"""Share of the train module's device time in operations under no ``dv3/`` scope."""

from benchmarks import scopes


def read(run):
    parts = scopes.train_parts(run)
    return 100.0 * parts["unscoped"] / parts["module"] if parts and parts["module"] else None
