"""Acting state one env keeps on the device: delta-rule state, convolution tail, key-value cache."""

from benchmarks import seq_scopes


def read(run):
    counts = seq_scopes.core_counts(run)
    nbytes = counts.get("state_bytes_per_env") if counts else None
    return nbytes / 2**20 if nbytes else None
