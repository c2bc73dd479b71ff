"""Episode ends inside a sampled window row, averaged over the window's gradient steps."""

from benchmarks import seq_scopes


def read(run):
    counts = seq_scopes.core_counts(run)
    if not counts:
        return None
    return counts["episode_ends"] / (counts["steps"] * run.config["sizes"]["batch_size"])
