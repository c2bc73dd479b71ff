"""Learning-health plane tests (``sheeprl_tpu/obs/learn``,
``howto/learning_health.md``).

- probe correctness: ``learn_probes`` values against hand-computed norms on a
  tiny two-module model (per-module/global grad norm, param norm,
  update-to-weight ratio, clip fraction, non-finite leaf count), including
  the p2e_dv3 shape where one module is a dict of per-k critic pytrees;
- sentinel grading: a synthetic explosion fires ``warn`` on the first
  excursion and ``critical`` (sustained_explosion) BEFORE any NaN sample
  arrives — the acceptance ordering — plus update-ratio collapse warns,
  non-finite handling, the anomaly-exclusion rule (the baseline must not
  chase the explosion), and the flight-recorder/counters side effects;
- zero cost when off: without an installed sentinel ``probes_enabled`` is
  False, ``observe_probes`` is a no-op, and the ``learn_probe_fetches``
  counter stays 0; with one installed, a burst costs exactly ONE fetch;
- fused-vs-per-step parity: the burst engine's stacked ``learn/`` buffers are
  bitwise identical between the fused dispatch and
  ``SHEEPRL_TRAIN_NO_FUSE=1`` (same compiled program wrote every row);
- the unified run report (``tools/run_report.py``) golden-checked against the
  committed mini-run fixtures, including the ``--compare`` verdict and exit
  code.
"""

import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.obs import learn as obs_learn
from sheeprl_tpu.obs.learn import LearnSentinel, learn_probes, split_probes

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
TOOLS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "tools"
)


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(TOOLS, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# -- probes: hand-computed values ---------------------------------------------


def test_learn_probes_hand_computed_norms():
    """Tiny two-module model: every probe equals the hand-computed value.
    Computed under jit — the probes live inside the train program."""
    grads = {
        "actor": {"w": jnp.asarray([3.0, 4.0])},  # norm 5
        "critic": {"w": jnp.asarray([[2.0], [2.0], [2.0], [2.0]])},  # norm 4
    }
    params = {
        "actor": {"w": jnp.asarray([6.0, 8.0])},  # norm 10
        "critic": {"w": jnp.zeros((4, 1))},
    }
    updates = {
        "actor": {"w": jnp.asarray([0.3, 0.4])},  # norm 0.5
        "critic": {"w": jnp.zeros((4, 1))},
    }
    out = jax.jit(
        lambda g, p, u: learn_probes(
            g, params=p, updates=u, losses=(jnp.float32(1.0),),
            clip_norms={"actor": 4.5, "critic": None},
        )
    )(grads, params, updates)
    out = jax.device_get(out)
    np.testing.assert_allclose(out["learn/grad_norm/actor"], 5.0, rtol=1e-6)
    np.testing.assert_allclose(out["learn/grad_norm/critic"], 4.0, rtol=1e-6)
    np.testing.assert_allclose(out["learn/grad_norm"], math.sqrt(25 + 16), rtol=1e-6)
    np.testing.assert_allclose(out["learn/param_norm"], 10.0, rtol=1e-6)
    np.testing.assert_allclose(out["learn/update_ratio"], 0.05, rtol=1e-5)
    # only the actor is clip-configured; 5 > 4.5 → 1/1 clipped
    np.testing.assert_allclose(out["learn/clip_frac"], 1.0)
    assert out["learn/nonfinite"] == 0.0
    assert all(k.startswith("learn/") for k in out)


def test_learn_probes_clip_frac_counts_only_configured_modules():
    grads = {
        "a": {"w": jnp.asarray([3.0, 4.0])},  # norm 5
        "b": {"w": jnp.asarray([1.0, 0.0])},  # norm 1
        "c": {"w": jnp.asarray([2.0, 0.0])},  # not clip-configured
    }
    out = jax.device_get(learn_probes(grads, clip_norms={"a": 4.0, "b": 10.0}))
    # a exceeded (5 > 4), b did not (1 < 10), c not counted → 1/2
    np.testing.assert_allclose(out["learn/clip_frac"], 0.5)
    out = jax.device_get(learn_probes(grads))
    np.testing.assert_allclose(out["learn/clip_frac"], 0.0)


def test_learn_probes_nonfinite_counts_grad_leaves_and_losses():
    grads = {
        "m": {
            "ok": jnp.asarray([1.0, 2.0]),
            "bad": jnp.asarray([1.0, jnp.nan]),
        },
    }
    out = jax.device_get(
        learn_probes(grads, losses=(jnp.float32(jnp.inf), jnp.float32(0.5)))
    )
    # one grad leaf with a NaN + one non-finite loss entry
    assert out["learn/nonfinite"] == 2.0


def test_learn_probes_module_value_may_be_dict_of_pytrees():
    """The p2e_dv3 per-k exploration critics fold into ONE module whose value
    is a dict of per-critic pytrees — the norm spans all of them."""
    grads = {
        "critics_exploration": {
            "intrinsic": {"w": jnp.asarray([3.0])},
            "extrinsic": {"w": jnp.asarray([4.0])},
        },
    }
    out = jax.device_get(learn_probes(grads))
    np.testing.assert_allclose(out["learn/grad_norm/critics_exploration"], 5.0, rtol=1e-6)
    np.testing.assert_allclose(out["learn/grad_norm"], 5.0, rtol=1e-6)


def test_split_probes_partitions_on_prefix():
    metrics = {"Loss/x": 1.0, "learn/grad_norm": 2.0, "learn/clip_frac": 0.0}
    rest, learn = split_probes(metrics)
    assert set(rest) == {"Loss/x"}
    assert set(learn) == {"learn/grad_norm", "learn/clip_frac"}
    same, none = split_probes({"Loss/x": 1.0})
    assert none is None and set(same) == {"Loss/x"}
    arr, none = split_probes(jnp.zeros(3))
    assert none is None and arr.shape == (3,)


# -- sentinel -----------------------------------------------------------------


class _FakeFlight:
    def __init__(self):
        self.triggers = []

    def trigger(self, reason, context=None):
        self.triggers.append((reason, context))


def _warmed_sentinel(flight=None, **cfg):
    base = {"warn_z": 4.0, "critical_z": 8.0, "warmup": 20, "critical_streak": 3}
    base.update(cfg)
    s = LearnSentinel(base, flight=flight)
    # flat baseline around 1.0: with the 0.05-decade std floor, z(v) is
    # simply log10(v) / 0.05 — warn above ~1.58, critical above ~2.51
    s.observe({"learn/grad_norm": np.ones(40)})
    return s


def test_sentinel_flat_baseline_stays_quiet():
    s = _warmed_sentinel()
    s.observe({"learn/grad_norm": np.asarray([1.02, 0.98, 1.1, 0.93])})
    assert s.warnings == 0 and s.criticals == 0


def test_sentinel_warns_on_excursion_and_criticals_before_nan():
    """The acceptance-criteria ordering at unit scale: an exploding grad-norm
    series fires warn, then critical (sustained_explosion), all BEFORE the
    first non-finite sample arrives — and the critical's timestamp precedes
    ``first_nonfinite_ts``."""
    flight = _FakeFlight()
    s = _warmed_sentinel(flight=flight)
    # moderate excursion: z = log10(3)/0.05 ≈ 9.5 > critical_z starts the
    # streak; use a milder 2.0 (z ≈ 6) for a plain warn first
    s.observe({"learn/grad_norm": np.asarray([2.0])})
    assert s.warnings == 1 and s.criticals == 0
    assert s.events[0]["severity"] == "warn"
    assert s.events[0]["reason"] == "grad_norm_excursion"
    # sustained explosion: 3 consecutive samples far above baseline
    s.observe({"learn/grad_norm": np.asarray([50.0, 80.0, 120.0])})
    assert s.criticals == 1
    crit = [e for e in s.events if e["severity"] == "critical"][0]
    assert crit["reason"] == "sustained_explosion"
    assert s.first_nonfinite_ts is None  # critical fired with NO NaN seen yet
    # ... and only now does the NaN land
    s.observe({"learn/grad_norm": np.asarray([np.nan])})
    assert s.first_nonfinite_ts is not None
    assert crit["ts_unix"] <= s.first_nonfinite_ts
    # every event also hit the flight recorder's learn_divergence trigger
    assert flight.triggers and all(r == "learn_divergence" for r, _ in flight.triggers)


def test_sentinel_streak_below_threshold_warns_not_criticals():
    s = _warmed_sentinel(critical_streak=3)
    s.observe({"learn/grad_norm": np.asarray([50.0, 50.0])})  # streak 2 < 3
    assert s.criticals == 0 and s.warnings == 2


def test_sentinel_update_ratio_collapse_warns():
    s = LearnSentinel({"warmup": 20})
    s.observe({"learn/update_ratio": np.full(40, 1e-3)})
    s.observe({"learn/update_ratio": np.asarray([1e-6])})  # z ≈ -60
    assert s.warnings == 1
    assert s.events[0]["reason"] == "update_ratio_collapse"
    # collapse is one-sided: a HIGH ratio is a grad-norm problem, not this one
    s2 = LearnSentinel({"warmup": 20})
    s2.observe({"learn/update_ratio": np.full(40, 1e-3)})
    s2.observe({"learn/update_ratio": np.asarray([1.0])})
    assert s2.warnings == 0


def test_sentinel_nonfinite_grads_critical_immediately():
    """The in-jit non-finite count shortcuts the z-machinery: any positive
    ``learn/nonfinite`` sample is critical on the spot, warmup or not."""
    s = LearnSentinel()
    s.observe({"learn/nonfinite": np.asarray([0.0, 0.0, 1.0])})
    assert s.criticals == 1
    assert s.events[0]["reason"] == "nonfinite_grads"
    assert s.first_nonfinite_ts is not None


def test_sentinel_on_nonfinite_metric_terminal_stage():
    s = LearnSentinel()
    s.on_nonfinite("Loss/value_loss", float("nan"))
    assert s.criticals == 1
    assert s.events[0]["reason"] == "nonfinite_metric"
    assert s.events[0]["probe"] == "metric:Loss/value_loss"
    assert s.first_nonfinite_ts is not None


def test_sentinel_baseline_does_not_chase_the_explosion():
    """Anomalous samples (z > critical_z) are excluded from the baseline: a
    second explosion right after the first must grade just as loudly."""
    s = _warmed_sentinel()
    base = s._baselines["learn/grad_norm"]
    mean_before, n_before = base.mean, base.n
    s.observe({"learn/grad_norm": np.full(6, 1000.0)})
    assert base.mean == pytest.approx(mean_before)
    assert base.n == n_before
    assert s.criticals >= 2  # streak kept re-arming at full sensitivity


def test_sentinel_summary_shape():
    s = _warmed_sentinel()
    s.observe({"learn/grad_norm": np.asarray([50.0, 50.0, 50.0])})
    doc = s.summary()
    assert doc["warnings"] == s.warnings and doc["criticals"] == 1
    assert doc["bursts_observed"] == 0  # observe() direct: no due_burst calls
    probe = doc["probes"]["learn/grad_norm"]
    assert probe["n"] == 40 and probe["p50"] is not None
    event = doc["events"][0]
    assert {"severity", "probe", "reason", "value", "z", "step", "ts_unix"} <= set(event)
    # summary must round-trip through json (it lands in telemetry.json)
    json.dumps(doc)


# -- zero cost when off -------------------------------------------------------


def test_probes_enabled_iff_sentinel_installed():
    assert obs_learn.installed() is None
    assert not obs_learn.probes_enabled()
    s = LearnSentinel()
    obs_learn.install(s)
    try:
        assert obs_learn.probes_enabled()
        assert obs_learn.installed() is s
    finally:
        obs_learn.install(None)
    assert not obs_learn.probes_enabled()


def test_observe_probes_costs_nothing_when_off_and_one_fetch_when_on():
    from sheeprl_tpu.obs import counters as obs_counters

    c = obs_counters.Counters()
    obs_counters.install(c)
    # off: no sentinel → no fetch, even with probes in hand
    obs_learn.observe_probes({"learn/grad_norm": np.ones(4)})
    assert c.learn_probe_fetches == 0
    # on: one burst = exactly one fetch; every_n_bursts=2 halves the cadence
    s = LearnSentinel({"every_n_bursts": 2, "warmup": 2})
    obs_learn.install(s)
    try:
        obs_learn.observe_probes({"learn/grad_norm": np.ones(4)})
        assert c.learn_probe_fetches == 1
        obs_learn.observe_probes({"learn/grad_norm": np.ones(4)})  # off-cadence
        assert c.learn_probe_fetches == 1
        obs_learn.observe_probes({"learn/grad_norm": np.ones(4)})
        assert c.learn_probe_fetches == 2
        # None probes (program built with probes off) never count a burst
        before = s._bursts_seen
        obs_learn.observe_probes(None)
        assert s._bursts_seen == before and c.learn_probe_fetches == 2
    finally:
        obs_learn.install(None)


# -- burst engine: stacked probes, fused vs per-step --------------------------


class _CaptureSentinel:
    """Duck-typed sentinel standing in for LearnSentinel: records the raw
    probe pytrees observe_probes hands over (post device_get)."""

    def __init__(self):
        self.seen = []

    def due_burst(self):
        return True

    def observe(self, probes, step=None):
        self.seen.append(probes)


def _probe_train_program():
    """A tiny but real TrainProgram whose step computes learn probes from its
    own grads/updates, plus the matching fresh agent state."""
    from sheeprl_tpu.fabric import Fabric
    from sheeprl_tpu.train import build_train_burst

    fabric = Fabric(devices=1, accelerator="cpu")

    def loss_fn(params, batch):
        pred = batch * params["m"]["w"]
        return jnp.sum(jnp.square(pred - 1.0))

    def local_step(agent_state, data, key):
        params = agent_state["params"]
        loss, grads = jax.value_and_grad(loss_fn)(params, data)
        updates = jax.tree_util.tree_map(lambda g: -0.01 * g, grads)
        new_params = jax.tree_util.tree_map(jnp.add, params, updates)
        noise = jax.random.uniform(key, ())  # key must thread per step
        metrics = {"Loss/x": loss + 0.0 * noise}
        metrics.update(
            learn_probes(
                {"m": grads["m"]},
                params={"m": params["m"]},
                updates={"m": updates["m"]},
                losses=(loss,),
                clip_norms={"m": 1.0},
            )
        )
        return {"params": new_params}, metrics

    program = build_train_burst(local_step, fabric, n_scanned=1, data_dim=0)
    state = {"params": {"m": {"w": jnp.asarray([0.5, 2.0])}}}
    return program, state


def _run_probe_burst(n=4):
    from sheeprl_tpu.train import run_train_burst

    program, state = _probe_train_program()
    data = jnp.reshape(jnp.arange(n * 2, dtype=jnp.float32), (n, 2)) / 7.0
    keys = jax.random.split(jax.random.PRNGKey(0), n)
    cap = _CaptureSentinel()
    obs_learn.install(cap)
    try:
        state, metrics = run_train_burst(
            program, state, data, (keys,), world_size=1, fetch_metrics=True
        )
    finally:
        obs_learn.install(None)
    assert len(cap.seen) == 1
    return jax.device_get(state), metrics, cap.seen[0]


def test_burst_stacks_probes_and_strips_them_from_metrics(monkeypatch):
    monkeypatch.delenv("SHEEPRL_TRAIN_NO_FUSE", raising=False)
    state, metrics, probes = _run_probe_burst(n=4)
    # the learn keys were split off before the metric fetch...
    assert set(metrics) == {"Loss/x"}
    # ...and arrive stacked [n] at the sentinel, one row per gradient step
    assert set(probes) == {
        "learn/grad_norm",
        "learn/grad_norm/m",
        "learn/param_norm",
        "learn/update_ratio",
        "learn/clip_frac",
        "learn/nonfinite",
    }
    for k, v in probes.items():
        assert np.shape(v) == (4,), k
    assert np.all(np.isfinite(probes["learn/grad_norm"]))
    # params drift step to step, so the stacked rows must differ
    assert len(np.unique(probes["learn/param_norm"])) == 4


def test_burst_probes_fused_bitwise_per_step(monkeypatch):
    """The stacked probe buffers AND the final state are bitwise identical
    between the fused burst and SHEEPRL_TRAIN_NO_FUSE=1 — both modes run the
    same compiled program, so every probe row is written by the same ops."""
    monkeypatch.delenv("SHEEPRL_TRAIN_NO_FUSE", raising=False)
    state_f, _, probes_f = _run_probe_burst(n=4)
    monkeypatch.setenv("SHEEPRL_TRAIN_NO_FUSE", "1")
    state_p, _, probes_p = _run_probe_burst(n=4)
    assert set(probes_f) == set(probes_p)
    for k in probes_f:
        np.testing.assert_array_equal(probes_f[k], probes_p[k], err_msg=k)
    np.testing.assert_array_equal(
        state_f["params"]["m"]["w"], state_p["params"]["m"]["w"]
    )


def test_probes_disabled_program_carries_no_learn_keys(monkeypatch):
    """An uninstrumented run's train program has no learn keys at all: the
    burst returns plain metrics and observe_probes never fetches."""
    from sheeprl_tpu.fabric import Fabric
    from sheeprl_tpu.obs import counters as obs_counters
    from sheeprl_tpu.train import build_train_burst, run_train_burst

    monkeypatch.delenv("SHEEPRL_TRAIN_NO_FUSE", raising=False)
    fabric = Fabric(devices=1, accelerator="cpu")

    def local_step(agent_state, data, key):
        # the algos gate on probes_enabled(cfg) at build time; with no
        # sentinel installed this branch compiles to nothing
        metrics = {"Loss/x": jnp.sum(data)}
        if obs_learn.probes_enabled():
            metrics.update(learn_probes({"m": agent_state["params"]}))
        return agent_state, metrics

    program = build_train_burst(local_step, fabric, n_scanned=1, data_dim=0)
    c = obs_counters.Counters()
    obs_counters.install(c)
    state = {"params": {"w": jnp.ones(2)}}
    data = jnp.ones((3, 2))
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    state, metrics = run_train_burst(
        program, state, data, (keys,), world_size=1, fetch_metrics=True
    )
    assert set(metrics) == {"Loss/x"}
    assert c.learn_probe_fetches == 0


# -- run_report golden --------------------------------------------------------


def test_run_report_golden_on_fixture(tmp_path):
    run_report = _load_tool("run_report")
    fixture = os.path.join(FIXTURES, "mini_run")
    rep = run_report.build_report(run_report.collect(fixture))
    lh = rep["learning_health"]
    assert lh["warnings"] == 2 and lh["criticals"] == 1
    assert lh["grad_norm_p95"] == 3.4
    assert lh["flight_dumps"] == ["flight_learn_divergence_1792.json"]
    assert rep["roofline"]["verdict"] == "host-bound"
    assert rep["eval"]["final"]["mean"] == 35.0
    assert rep["eval"]["inrun_rounds"] == 2

    text = run_report.render_markdown(rep)
    # the four acceptance sections, each populated from the fixture
    assert "## Learning health" in text
    assert "CRITICAL — divergence events fired" in text
    assert "sustained_explosion" in text
    assert "flight_learn_divergence_1792.json" in text
    assert "## Phase percentiles" in text and "| train |" in text
    assert "## Roofline" in text and "host-bound" in text
    assert "## Evaluation" in text and "**35**" in text

    # CLI writes report.md (+ --json) into --out's directory
    out = tmp_path / "report.md"
    rc = run_report.main([fixture, "--out", str(out), "--json"])
    assert rc == 0
    assert "CRITICAL" in out.read_text()
    doc = json.loads((tmp_path / "report.json").read_text())
    assert doc["learning_health"]["criticals"] == 1


def test_run_report_missing_artifacts_never_crash(tmp_path):
    run_report = _load_tool("run_report")
    rep = run_report.build_report(run_report.collect(str(tmp_path)))
    assert rep["has_summary"] is False
    text = run_report.render_markdown(rep)
    assert "No `telemetry.json` found" in text
    assert "not instrumented" in text


def test_run_report_compare_flags_the_spike_run(capsys):
    run_report = _load_tool("run_report")
    spike = os.path.join(FIXTURES, "mini_run")
    clean = os.path.join(FIXTURES, "mini_run_clean")
    rc = run_report.main([spike, "--compare", clean])
    text = capsys.readouterr().out
    assert rc == 1  # non-blocking-red semantics, like bench_compare
    assert "`mini_run` is the unstable run" in text
    # same run against itself: no difference, exit 0
    rc = run_report.main([clean, "--compare", clean])
    text = capsys.readouterr().out
    assert rc == 0 and "no learning-health difference" in text
