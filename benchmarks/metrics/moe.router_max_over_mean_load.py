"""Largest load among all of the router's outputs, held here or not, in any routing layer, over the mean load (a step's tokens times the experts a token over the router's outputs), averaged over the window's gradient steps: what the balance step exists to move."""

from benchmarks import seq_scopes


def read(run):
    counts = seq_scopes.core_counts(run)
    if not counts or not counts.get("router_max_load"):
        return None
    sizes = run.config["sizes"]
    tokens = 2.0 * sizes["sequence_length"] * sizes["batch_size"] * run.chips
    mean = tokens * sizes["num_experts_per_tok"] / sizes["router_outputs"]
    return (counts["router_max_load"] / counts["steps"]) / mean
