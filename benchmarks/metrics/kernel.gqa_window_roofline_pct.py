"""The window pass's grouped-query attention scores' share of their roofline: the least time for the operations of the query-key pairs inside an episode's segment (the program's counter; forward and backward) and q, k, v, o once each way, over the device time of the train module's ``attn/scores`` operations."""

from benchmarks import lfm2_scopes, seq_scopes


def read(run):
    work, counts = seq_scopes.work_counts(run), seq_scopes.core_counts(run)
    if work is None or not hasattr(work, "gqa_window_work") or not counts or not counts.get("attended_pairs"):
        return None
    sizes, traced = run.config["sizes"], run.recorder.grad_steps
    pairs = counts["attended_pairs"] / counts["steps"] * traced
    tokens = 2.0 * sizes["sequence_length"] * sizes["batch_size"] * work.layers_of(sizes)["attn"] * traced
    return lfm2_scopes.roofline_pct(run, "kernel/gqa_scores", *work.gqa_window_work(sizes, pairs, tokens))
