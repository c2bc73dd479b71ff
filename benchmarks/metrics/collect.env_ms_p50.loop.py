"""Median environment step, per env: the env-interaction span over the vector's envs."""

from benchmarks import reduce


def read(run):
    return reduce.p50([v / run.n_envs for v in run.span_ms("Time/env_interaction_time")])
