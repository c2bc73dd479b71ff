"""Device time of the train program's module in the trace, per gradient step."""


def read(run):
    seconds = run.train_device_seconds()
    if seconds is None or not run.recorder.grad_steps:
        return None
    return seconds * 1e3 / run.recorder.grad_steps
