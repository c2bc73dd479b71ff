"""The absorbed one-token attention's share of its roofline: the least time for the operations of the latent positions imagination's steps attended to and one read of the latent cache (a row's shared window cache once a step and layer, a stream's own ring once; the program's counters), over the device time of the train module's ``latent_decode`` operations."""

from benchmarks import mla_scopes, seq_scopes


def read(run):
    work, counts = seq_scopes.work_counts(run), seq_scopes.core_counts(run)
    names = ("decode_context_tokens", "decode_cache_tokens", "imagination_starts", "decode_steps")
    if work is None or not hasattr(work, "mla_decode_work") or not counts or not all(counts.get(n) for n in names):
        return None
    sizes, traced, steps = run.config["sizes"], run.recorder.grad_steps, counts["steps"]
    stream_steps = (counts["imagination_starts"] / steps) * (counts["decode_steps"] / steps) \
        * sizes["num_hidden_layers"] * traced
    return mla_scopes.roofline_pct(
        run, "kernel/latent_decode",
        *work.mla_decode_work(sizes, counts["decode_context_tokens"] / steps * traced,
                              counts["decode_cache_tokens"] / steps * traced, stream_steps),
    )
