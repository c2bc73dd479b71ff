"""Build and dispatch fused train bursts.

A *train burst* is one replay-staged block of ``n_samples`` gradient steps.
The per-step shape — ``for i in range(n_samples): train_fn(state, batch[i],
...)`` — pays one host→device dispatch per gradient step, each marshalling
every leaf of the donated state. :func:`build_train_burst` wraps a
single-gradient-step function into a
:class:`TrainProgram` whose ``.burst`` runs the whole block as ONE jitted
``lax.scan`` program: the agent state rides the scan carry (donated, so
optimizer/ensemble state never round-trips), while everything that varies per
step — the staged ``[n_samples, ...]`` batch stack, per-step PRNG keys, and
host-computed scalar schedules such as the target-update ``tau`` cadence —
is scanned over as arrays.

Determinism contract: the burst program's loop bound is a runtime scalar,
so the fused dispatch (count=n) and a sequential per-step loop (n dispatches
of count=1) execute the same while-loop body of the same executable over
the same ``(batch, key, schedule)`` tuples — bitwise identical BY
CONSTRUCTION under fixed seeds (checkpoint state compared;
``tests/test_algos`` holds the per-family proof). Setting
``SHEEPRL_TRAIN_NO_FUSE=1`` makes :func:`run_train_burst` dispatch that
sequential reference loop instead — same staged stack, same key discipline —
which is both the parity-test harness and the per-step side of the
``dv2_train_burst_sps`` bench line.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from sheeprl_tpu.obs import get_telemetry, register_train_cost, shape_specs, span
from sheeprl_tpu.obs import learn as _learn
from sheeprl_tpu.obs.counters import add_train_burst
from sheeprl_tpu.obs.learn import split_probes
from sheeprl_tpu.obs.spans import scoped_compile_key


class TrainProgram:
    """One-gradient-step program plus the fused whole-burst variant.

    Callable like the plain step (existing tests/benches and the per-step
    reference loop), with ``.burst`` for the scan-over-samples program the
    train loops dispatch.
    """

    def __init__(self, step_fn, burst_fn):
        self._step = step_fn
        self.burst = burst_fn

    def __call__(self, *args, **kwargs):
        return self._step(*args, **kwargs)

    def lower(self, *args, **kwargs):
        return self._step.lower(*args, **kwargs)


def build_train_burst(
    local_step: Callable,
    fabric,
    *,
    n_scanned: int = 1,
    data_dim: int = 1,
    plan=None,
    metric_mode: str = "last",
) -> TrainProgram:
    """Wrap a single-gradient-step function into a :class:`TrainProgram`.

    ``local_step(agent_state, data, *scanned) -> (agent_state, metrics)`` is
    the *pre-shard_map* per-step function: ``data`` is one step's batch with
    the sharded axis at position ``data_dim`` (1 for ``[T, B, ...]`` sequence
    batches, 0 for ``[B, ...]`` transition batches) and ``scanned`` are
    ``n_scanned`` per-step scalars (the PRNG key, then any host-computed
    schedules such as ``tau``). Both compiled variants donate the agent
    state:

    - the step program shards ``data`` over the batch axis and runs one
      gradient step (``shard_map`` on the data mesh, or the GSPMD ``plan``
      path when a sharding plan is provided);
    - the burst program ``burst(state, data_stack, start, count, *scanned)``
      runs gradient steps ``start..start+count-1`` over the stacked
      ``[n_samples, ...]`` batches and scanned arrays as ONE dispatch, the
      state riding the loop carry, and reduces the per-step metrics on
      device per ``metric_mode`` (``"last"`` — what the aggregator consumed
      under the sequential loop — ``"mean"``, or ``"stack"``). ``start`` and
      ``count`` are runtime scalars, so one compiled program serves every
      burst length — and the per-step reference mode (see
      :func:`run_train_burst`) bitwise-matches the fused mode by
      construction. It returns ``(state, metrics)`` and nothing else.
    """
    if metric_mode not in ("last", "mean", "stack"):
        raise ValueError(f"metric_mode must be last|mean|stack, got {metric_mode!r}")
    data_axis = fabric.data_axis
    step_data_dims = [None] * int(data_dim) + [data_axis]

    def local_burst(agent_state, data_stack, start, count, *scanned):
        # The loop bound is DYNAMIC (a runtime scalar, not a trace constant):
        # ONE compiled program serves both the fused burst (start=0, count=n)
        # and the per-step reference loop (n dispatches of count=1). That is
        # what makes the two modes bitwise identical BY CONSTRUCTION — two
        # differently-jitted programs of the same math may legally differ in
        # the last ulp (XLA fuses a scan body, a standalone step, and a
        # trip-count-1 loop differently; measured ~1e-9 drift on CPU), but
        # here every gradient step executes the same while-loop body of the
        # same executable. Same trick as the rollout engine's dynamic-length
        # acting burst (envs/rollout/burst.py).
        def at(i, tree):
            return jax.tree_util.tree_map(
                lambda x: jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False), tree
            )

        # abstract-eval one step to build the metric carry structure; the
        # learn-probe keys (obs/learn, "learn/" prefix) are split out and
        # ALWAYS stack-accumulated — the sentinel grades every per-step
        # sample, so "last"/"mean" reductions would hide exactly the
        # excursions it exists to catch
        metric_shapes = jax.eval_shape(
            local_step, agent_state, at(0, data_stack), *at(0, scanned)
        )[1]
        metric_shapes, learn_shapes = split_probes(metric_shapes)
        n_stack = (
            int(np.shape(jax.tree_util.tree_leaves(scanned[0])[0])[0])
            if (metric_mode == "stack" or learn_shapes)
            else 0
        )
        if metric_mode == "stack":
            init_metrics = jax.tree_util.tree_map(
                lambda s: jnp.zeros((n_stack,) + tuple(s.shape), s.dtype), metric_shapes
            )
        else:
            init_metrics = jax.tree_util.tree_map(
                lambda s: jnp.zeros(tuple(s.shape), s.dtype), metric_shapes
            )
        init_learn = (
            {
                k: jnp.zeros((n_stack,) + tuple(s.shape), s.dtype)
                for k, s in learn_shapes.items()
            }
            if learn_shapes
            else {}
        )

        def body(i, carry):
            state, metrics, learn = carry
            new_state, m = local_step(state, at(i, data_stack), *at(i, scanned))
            m, lm = split_probes(m)
            if lm:
                learn = {
                    k: jax.lax.dynamic_update_index_in_dim(learn[k], lm[k], i, 0)
                    for k in learn
                }
            if metric_mode == "last":
                metrics = m
            elif metric_mode == "mean":
                metrics = jax.tree_util.tree_map(jnp.add, metrics, m)
            else:
                metrics = jax.tree_util.tree_map(
                    lambda buf, v: jax.lax.dynamic_update_index_in_dim(buf, v, i, 0),
                    metrics,
                    m,
                )
            return (new_state, metrics, learn)

        state, metrics, learn = jax.lax.fori_loop(
            start, start + count, body, (agent_state, init_metrics, init_learn)
        )
        if metric_mode == "mean":
            denom = jnp.maximum(count, 1)
            metrics = jax.tree_util.tree_map(
                lambda x: x / denom.astype(x.dtype), metrics
            )
        if learn:
            metrics = {**metrics, **learn}
        return state, metrics

    if plan is None:
        step_fn = jax.jit(
            jax.shard_map(
                local_step,
                mesh=fabric.mesh,
                in_specs=(P(), P(*step_data_dims)) + (P(),) * n_scanned,
                out_specs=(P(), P()),
                check_vma=False,
            ),
            donate_argnums=(0,),
        )
        burst_fn = jax.jit(
            jax.shard_map(
                local_burst,
                mesh=fabric.mesh,
                in_specs=(P(), P(None, *step_data_dims), P(), P()) + (P(),) * n_scanned,
                out_specs=(P(), P()),
                check_vma=False,
            ),
            donate_argnums=(0,),
        )
    else:
        state_sh = plan.shardings()
        rep = fabric.replicated
        step_fn = jax.jit(
            local_step,
            in_shardings=(state_sh, fabric.sharding(*step_data_dims)) + (rep,) * n_scanned,
            out_shardings=(state_sh, rep),
            donate_argnums=(0,),
        )
        burst_fn = jax.jit(
            local_burst,
            in_shardings=(state_sh, fabric.sharding(None, *step_data_dims), rep, rep)
            + (rep,) * n_scanned,
            out_shardings=(state_sh, rep),
            donate_argnums=(0,),
        )
    return TrainProgram(step_fn, burst_fn)


def tau_schedule(
    n: int, start: int, every: int, *, tau: float = 1.0, first_hard: bool = True
) -> np.ndarray:
    """Host-side target-update schedule for gradient steps ``start..start+n-1``.

    Step ``g`` updates the target network (``tau`` on the cadence, 0.0 off
    it): hard-copy families (DV2) pass ``tau=1.0, first_hard=False``;
    EMA families (DV3) pass their soft ``tau`` with ``first_hard=True`` so
    the run's very first gradient step hard-copies regardless. A pretrain
    catch-up burst at ``learning_starts`` is just a large ``n`` — the
    cadence falls out of the same arithmetic.
    """
    g = int(start) + np.arange(int(n), dtype=np.int64)
    out = np.where(g % max(int(every), 1) == 0, np.float32(tau), np.float32(0.0))
    if first_hard:
        out = np.where(g == 0, np.float32(1.0), out)
    return out.astype(np.float32)


def metric_fetch_gate(
    cfg,
    aggregator,
    *,
    policy_step: int,
    last_log: int,
    train_step: int,
    update: int,
    num_updates: int,
    policy_steps_per_update: int,
    world_size: int,
) -> bool:
    """Should THIS burst's metrics be pulled to host? (DV3's gate, shared.)

    On a bandwidth-limited host link every blocking device→host metric fetch
    costs a round trip; ``metric.fetch_train_metrics_every=k`` samples the
    train metrics every k-th burst (always on the last burst before a log
    boundary), 1 = every burst (default), 0 = log boundaries only. Log
    boundaries are crossed by policy steps, not bursts, so look ahead one
    real burst period (bursts recur every
    ``max(train_every // policy_steps_per_update, 1)`` updates, NOT every
    ``train_every`` policy steps when the two don't divide): if the
    threshold falls before the next burst, this is the burst whose metrics
    that log will see.
    """
    if aggregator is None or aggregator.disabled:
        return False
    burst_updates = max(int(cfg.algo.train_every) // int(policy_steps_per_update), 1)
    burst_period = burst_updates * int(policy_steps_per_update)
    will_log = cfg.metric.log_level > 0 and (
        policy_step - last_log + burst_period >= cfg.metric.log_every
        # the run's last burst feeds the final update==num_updates log even
        # when that update itself is not a burst
        or update + burst_updates > num_updates
    )
    fetch_every = int(cfg.metric.get("fetch_train_metrics_every", 1))
    return will_log or (fetch_every > 0 and (train_step // world_size) % fetch_every == 0)


def fused_enabled() -> bool:
    """Fused dispatch unless ``SHEEPRL_TRAIN_NO_FUSE`` opts into the
    per-step reference loop (parity tests, bench per-step side)."""
    return os.environ.get("SHEEPRL_TRAIN_NO_FUSE", "0") in ("", "0")


def run_train_burst(
    train_fn: TrainProgram,
    agent_state: Any,
    data_stack: Any,
    scanned: Sequence[Any],
    *,
    world_size: int = 1,
    fetch_metrics: bool = True,
    pacing_metric: str = "Loss/world_model_loss",
) -> Tuple[Any, Optional[Any]]:
    """Dispatch one training burst and account for it.

    ``scanned`` are the per-step arrays (keys first, then schedules), each
    ``[n_samples, ...]``. Returns ``(agent_state, metrics_or_None)``:
    metrics are device_get-fetched only when ``fetch_metrics`` (the
    :func:`metric_fetch_gate` decision); otherwise one scalar is pulled as a
    pacing barrier that bounds dispatch run-ahead to one burst — the wait
    is the device's own step time — and ``None`` is returned.

    The burst is ONE device dispatch; ``register_train_cost`` therefore
    books its AOT cost at ``dispatches_per_step=1`` so MFU accounting stays
    unit-correct, and the ``train_bursts``/``train_dispatches`` counters
    record the dispatch economy the fusion buys. Under
    ``SHEEPRL_TRAIN_NO_FUSE=1`` the same burst runs as the sequential
    per-step reference loop (``n_samples`` dispatches, identical
    ``(batch, key, schedule)`` tuples → bitwise-identical state).

    Two spans split the burst where the host pays for it:
    ``Time/train_dispatch_time`` until the dispatch returns (every dispatch
    of the per-step loop under one span), and ``Time/train_sync_time``
    around the fetch or pacing pull, which ends with the burst's own outputs
    ready — dispatch + sync is the train time the host sees. The dispatch
    also runs under :func:`~sheeprl_tpu.obs.spans.scoped_compile_key`, so
    that a traced run's train program keeps its named scopes whatever the
    persistent compile cache holds.

    When the step's metrics carry ``learn/`` probe keys (obs/learn), the
    stacked probe subtree is split off before the fetch/pacing logic and fed
    to the installed sentinel — one extra scalar pull per burst at most,
    nothing when probes are off (the keys simply don't exist).
    """
    scanned = tuple(scanned)
    n = int(np.shape(scanned[0])[0])
    telemetry = get_telemetry()
    want_cost = telemetry is not None and telemetry.needs_train_flops()
    if fused_enabled():
        burst_args = (agent_state, data_stack, np.int32(0), np.int32(n)) + scanned
        # specs captured pre-call: the burst donates agent_state
        specs = shape_specs(burst_args) if want_cost else None
        with span("Time/train_dispatch_time", phase="train"), scoped_compile_key():
            agent_state, metrics = train_fn.burst(*burst_args)
        metrics, learn_dev = split_probes(metrics)
        add_train_burst(steps=n, dispatches=1)
        if specs is not None:
            # one AOT cost analysis of the burst program (FLOPs + bytes
            # accessed), registered per train-step UNIT; the documented
            # while-body-once caveat (obs/perf.py) applies as it did to the
            # scan-based DV3 burst this engine generalizes
            register_train_cost(telemetry, train_fn.burst, *specs, world_size=world_size)
    else:
        # the reference loop dispatches the SAME compiled program n times
        # with count=1 — one dispatch per gradient step, every step running
        # the identical while-loop body. The full stacks are passed each
        # time (already committed on device: no re-upload), only start moves.
        specs = None
        metrics = None
        learn_rows = []
        with span("Time/train_dispatch_time", phase="train"), scoped_compile_key():
            for i in range(n):
                step_args = (agent_state, data_stack, np.int32(i), np.int32(1)) + scanned
                if specs is None and want_cost:
                    specs = shape_specs(step_args)
                agent_state, metrics = train_fn.burst(*step_args)
                metrics, learn_i = split_probes(metrics)
                if learn_i:
                    # each count=1 call writes exactly slot i of its [n] learn
                    # buffers; that row is bitwise the fused stack's row i
                    # (same executable wrote it)
                    learn_rows.append(
                        jax.tree_util.tree_map(
                            lambda x: jax.lax.index_in_dim(x, i, 0, keepdims=False),
                            learn_i,
                        )
                    )
        learn_dev = (
            {k: jnp.stack([r[k] for r in learn_rows]) for k in learn_rows[0]}
            if learn_rows
            else None
        )
        add_train_burst(steps=n, dispatches=n)
        if specs is not None:
            register_train_cost(
                telemetry,
                train_fn.burst,
                *specs,
                world_size=world_size,
                dispatches_per_step=n,
            )
    with span("Time/train_sync_time", phase="train"):
        # learn-probe feed: at most ONE extra device_get per burst (cadence-
        # and install-gated inside observe_probes; uninstrumented runs see no
        # learn keys at all and pay nothing here)
        _learn.observe_probes(learn_dev)
        if metrics is not None and fetch_metrics:
            metrics = jax.device_get(metrics)
        elif metrics is not None:
            leaf = metrics.get(pacing_metric) if isinstance(metrics, dict) else None
            if leaf is None:
                leaf = jax.tree_util.tree_leaves(metrics)[0]
            np.asarray(leaf)
            metrics = None
    return agent_state, metrics
