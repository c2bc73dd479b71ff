"""Largest load of a held expert over the mean load, averaged over the window's gradient steps and layers."""

from benchmarks import seq_scopes


def read(run):
    counts = seq_scopes.core_counts(run)
    if not counts or not counts.get("held_pairs"):
        return None
    sizes = run.config["sizes"]
    mean = counts["held_pairs"] / (counts["steps"] * sizes["num_hidden_layers"] * sizes["num_experts"])
    return (counts["max_load"] / counts["steps"]) / mean
