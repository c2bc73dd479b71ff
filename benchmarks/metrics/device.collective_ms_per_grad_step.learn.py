"""Self time of collective operations in the trace, per gradient step (cells on several chips)."""


def read(run):
    summary = run.device_summary()
    if run.chips < 2 or summary is None or not run.recorder.grad_steps:
        return None
    return summary["collective_s"] * 1e3 / run.recorder.grad_steps
