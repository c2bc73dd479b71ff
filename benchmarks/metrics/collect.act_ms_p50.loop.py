"""Median acting call on the parameter mirror: the rollout span less the env step inside it."""

from benchmarks import reduce


def read(run):
    acting = run.span_ms("Time/rollout_time")
    env = run.span_ms("Time/env_interaction_time")
    if not acting or len(env) < len(acting):
        return None
    # the acting span wraps the environment step it ends in
    return reduce.p50([a - e for a, e in zip(acting, env[-len(acting):])])
