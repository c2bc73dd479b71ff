"""The grouped expert products' share of their roofline: the least time for the operations and bytes of the pairs the router sent to held experts, in the window passes and in imagination's one-token steps alike, over the device time of the train module's ``ragged-dot`` operations."""

from benchmarks import seq_scopes


def read(run):
    work, counts = seq_scopes.work_counts(run), seq_scopes.core_counts(run)
    names = ("held_pairs", "experts_hit", "imagination_pairs", "imagination_experts_hit")
    if work is None or not counts or not all(counts.get(name) for name in names):
        return None
    traced = run.recorder.grad_steps
    routed = [counts[name] / counts["steps"] * traced for name in names]
    return seq_scopes.roofline_pct(
        run, "kernel/ragged_dot", *work.moe_grouped_work(run.config["sizes"], traced, *routed)
    )
