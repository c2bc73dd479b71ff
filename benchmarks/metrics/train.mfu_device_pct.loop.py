"""The whole step's share of the chip's bf16 peak: required FLOPs from shapes over the train module's device time."""

from benchmarks import reduce
from benchmarks.dv3_flops import flops_per_grad_step


def read(run):
    seconds = run.train_device_seconds()
    if not seconds or not run.recorder.grad_steps:
        return None
    flops = flops_per_grad_step(run.config["sizes"]) * run.recorder.grad_steps
    return 100.0 * flops / (seconds * reduce.peak_flops(run.device_kind))
