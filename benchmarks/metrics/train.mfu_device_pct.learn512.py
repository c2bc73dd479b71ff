"""The whole step's share of the chip's bf16 peak: required FLOPs (shapes and the program's counters of routed pairs, imagination starts and one-token steps) over the train module's device time."""

from benchmarks import reduce, seq_scopes


def read(run):
    seconds = run.train_device_seconds()
    work, counts = seq_scopes.work_counts(run), seq_scopes.core_counts(run)
    if not seconds or not run.recorder.grad_steps or work is None:
        return None
    names = {"held_pairs": "held_pairs", "imagination_starts": "streams", "decode_steps": "decode_steps"}
    counted = {arg: counts[k] / counts["steps"] for k, arg in names.items() if counts and counts.get(k)}
    flops = work.flops_per_grad_step(run.config["sizes"], **counted) * run.recorder.grad_steps
    return 100.0 * flops / (seconds * reduce.peak_flops(run.device_kind))
