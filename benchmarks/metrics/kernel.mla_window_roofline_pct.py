"""The window pass's attention scores' share of their roofline: the least time for the operations of the query-key pairs inside an episode's segment (the program's counter; forward and backward) and q, k, v, o once each way, over the device time of the train module's ``mla/scores`` operations."""

from benchmarks import mla_scopes, seq_scopes


def read(run):
    work, counts = seq_scopes.work_counts(run), seq_scopes.core_counts(run)
    if work is None or not hasattr(work, "mla_window_work") or not counts or not counts.get("attended_pairs"):
        return None
    sizes, traced = run.config["sizes"], run.recorder.grad_steps
    pairs = counts["attended_pairs"] / counts["steps"] * traced
    tokens = 2.0 * sizes["sequence_length"] * sizes["batch_size"] * sizes["num_hidden_layers"] * traced
    return mla_scopes.roofline_pct(run, "kernel/mla_scores", *work.mla_window_work(sizes, pairs, tokens))
