"""The gated short convolution's share of its roofline: the least time for the bytes of ``B``, ``u``, ``C`` and the output once each way, forward and backward, over the window passes' tokens and convolution layers, over the device time of the train module's ``conv/gate_conv`` operations in the window pass."""

from benchmarks import lfm2_scopes, seq_scopes


def read(run):
    work = seq_scopes.work_counts(run)
    if work is None or not hasattr(work, "shortconv_work"):
        return None
    sizes = run.config["sizes"]
    tokens = 2.0 * sizes["sequence_length"] * sizes["batch_size"] * work.layers_of(sizes)["conv"] * run.recorder.grad_steps
    return lfm2_scopes.roofline_pct(run, "kernel/gate_conv", *work.shortconv_work(sizes, tokens))
