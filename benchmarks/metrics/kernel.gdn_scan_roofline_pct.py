"""The delta rule's share of its roofline: the least time the chip could take for the recurrence's own operations and bytes over the device time of the ``delta_rule`` operations."""

from benchmarks import seq_scopes


def read(run):
    work = seq_scopes.work_counts(run)
    if work is None:
        return None
    sizes = run.config["sizes"]
    tokens = 2.0 * sizes["sequence_length"] * sizes["batch_size"] * run.recorder.grad_steps
    return seq_scopes.roofline_pct(run, "kernel/delta_rule", *work.gdn_scan_work(sizes, tokens))
