#!/usr/bin/env python
"""Telemetry-uniformity lint: every algorithm entrypoint must log its rate
gauges through the shared plumbing.

The ``Time/sps_*`` / ``Perf/mfu`` computation lives exactly once, in
``sheeprl_tpu/obs/perf.py`` (``log_sps_metrics``); before it existed the same
block was copy-pasted across all 17 entrypoints and had already drifted. This
lint fails when a file under ``sheeprl_tpu/algos/`` re-grows its own copy:

- a ``"Time/sps_..."`` or ``"Perf/mfu"`` string literal (hand-rolled gauge);
- a ``timer.compute()`` / ``timer.reset()`` call (private registry drain —
  the shared helper owns the read-and-reset cycle);
- a ``with timer(...)`` scope (use ``obs.span`` so the phase also reaches the
  trace timeline and XLA profiles);
- an ad-hoc wall-clock read (``time.time()`` / ``time.perf_counter()`` /
  ``time.monotonic()``, under any import alias) — the span phases already
  time the hot loops and feed the streaming histograms/flight recorder;
  private deltas measure the same thing invisibly. A slice of the loop
  that no span covers yet gets a span of its own (``Time/<what>_time`` with
  a ``phase``; the shared layers already bring ``train_dispatch``/
  ``train_sync``, ``publish``, ``replay_sample`` and ``stage_h2d``, the
  DreamerV3 loop ``replay_add``), never a second timing system;
- a ``log_sps_metrics`` call without a matching ``profile_tick`` call in
  the same file — the in-run device-profile scheduler (``obs/prof``)
  advances at the log boundary, so an entrypoint that logs rates but never
  ticks the profiler silently opts out of ``device_ms_per_step``/roofline
  coverage;
- a ``register_train_cost`` or ``build_train_burst`` call without
  ``learn_probes``/``observe_probes`` in the same file — an entrypoint that
  declares its train cost (or builds a burst program) without wiring the
  learning-health plane (``obs/learn``) ships no grad-norm/update-ratio
  telemetry and the divergence sentinel is blind to it
  (howto/learning_health.md);
- a raw collective — ``jax.lax.pmean``/``psum``/``all_gather``/... or a
  direct ``fabric.all_gather``/``broadcast``/``barrier``/``all_reduce``
  call — instead of the instrumented chokepoints in
  ``sheeprl_tpu/obs/dist/comms.py``: in-jit collectives must route through
  ``obs.dist.pmean``/``psum``/``instrumented_all_gather`` (so the xplane
  comms attribution is the agreed measurement and a future overlap rewrite
  is one edit), and host-level collectives through the fabric methods'
  measured spans only via shared infrastructure, never ad hoc in an algo.

The serving tier gets the same clock discipline: files under
``sheeprl_tpu/serve/`` may not read ``time.time()`` / ``time.monotonic()`` /
``time.perf_counter()`` directly — every request timestamp must come from
the sanctioned chokepoint ``sheeprl_tpu.obs.reqtrace.now`` / ``unix_now``,
so trace spans, latency histograms, and SLO burn windows stay on one
comparable clock (``time.sleep`` is fine — it is not a clock read).

AST-based, so comments and docstrings mentioning the metric names are fine.

Usage: ``python tools/lint_telemetry.py`` — exits non-zero with a findings
list on violation. Wired into the CI tier-1 lane (.github/workflows/tests.yml).
"""

from __future__ import annotations

import ast
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ALGOS_DIR = os.path.join(REPO, "sheeprl_tpu", "algos")
SERVE_DIR = os.path.join(REPO, "sheeprl_tpu", "serve")

FORBIDDEN_LITERAL_PREFIXES = ("Time/sps_", "Perf/mfu")
FORBIDDEN_TIMER_CALLS = ("compute", "reset")
FORBIDDEN_CLOCK_ATTRS = ("time", "perf_counter", "monotonic")
#: in-jit collective ops that must go through sheeprl_tpu/obs/dist/comms.py
FORBIDDEN_LAX_COLLECTIVES = (
    "pmean",
    "psum",
    "psum_scatter",
    "pmax",
    "pmin",
    "all_gather",
    "all_to_all",
    "ppermute",
)
#: host-level fabric collectives algos must not call ad hoc (shared
#: infrastructure — utils/, plane/, obs/ — owns those call sites)
FORBIDDEN_FABRIC_COLLECTIVES = ("all_gather", "all_reduce", "broadcast", "barrier")


def _is_lax_base(node: ast.AST) -> bool:
    """True for ``lax`` or ``jax.lax`` attribute bases."""
    if isinstance(node, ast.Name):
        return node.id == "lax"
    return (
        isinstance(node, ast.Attribute)
        and node.attr == "lax"
        and isinstance(node.value, ast.Name)
        and node.value.id == "jax"
    )


def _docstring_nodes(tree: ast.AST) -> set:
    """Constant nodes that are docstrings (allowed to mention metric names)."""
    allowed = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            body = getattr(node, "body", [])
            if body and isinstance(body[0], ast.Expr) and isinstance(body[0].value, ast.Constant):
                allowed.add(id(body[0].value))
    return allowed


def _clock_aliases(tree: ast.AST) -> tuple:
    """(module aliases of ``time``, names bound to its clock functions)."""
    modules = set()
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name == "time":
                    modules.add(alias.asname or alias.name)
        elif isinstance(node, ast.ImportFrom) and node.module == "time":
            for alias in node.names:
                if alias.name in FORBIDDEN_CLOCK_ATTRS:
                    names.add(alias.asname or alias.name)
    return modules, names


def _call_names(tree: ast.AST) -> dict:
    """Called-function name -> first call line number."""
    out: dict = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            fn = node.func
            name = fn.id if isinstance(fn, ast.Name) else (
                fn.attr if isinstance(fn, ast.Attribute) else None
            )
            if name is not None and name not in out:
                out[name] = node.lineno
    return out


def lint_file(path: str) -> list:
    src = open(path).read()
    tree = ast.parse(src, filename=path)
    docstrings = _docstring_nodes(tree)
    clock_modules, clock_names = _clock_aliases(tree)
    findings = []
    calls = _call_names(tree)
    if "log_sps_metrics" in calls and "profile_tick" not in calls:
        findings.append(
            (calls["log_sps_metrics"],
             "log_sps_metrics without profile_tick — the in-run profiler "
             "(sheeprl_tpu.obs.profile_tick) must advance at the same log "
             "boundary or this entrypoint has no device_ms_per_step/roofline "
             "coverage")
        )
    cost_call = calls.get("register_train_cost", calls.get("build_train_burst"))
    if cost_call is not None and "learn_probes" not in calls and "observe_probes" not in calls:
        findings.append(
            (cost_call,
             "train cost registered without learning-health probe wiring — "
             "compute sheeprl_tpu.obs.learn_probes inside the train step (or "
             "feed the host side via observe_probes) so the divergence "
             "sentinel covers this entrypoint (howto/learning_health.md)")
        )
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Constant)
            and isinstance(node.value, str)
            and id(node) not in docstrings
            and node.value.startswith(FORBIDDEN_LITERAL_PREFIXES)
        ):
            findings.append(
                (node.lineno,
                 f"hand-rolled {node.value!r} gauge — log rates through "
                 "sheeprl_tpu.obs.log_sps_metrics")
            )
        if isinstance(node, ast.Call):
            fn = node.func
            if (
                isinstance(fn, ast.Attribute)
                and isinstance(fn.value, ast.Name)
                and fn.value.id == "timer"
                and fn.attr in FORBIDDEN_TIMER_CALLS
            ):
                findings.append(
                    (node.lineno,
                     f"timer.{fn.attr}() drains the shared registry — "
                     "log_sps_metrics owns the read-and-reset cycle")
                )
            if isinstance(fn, ast.Name) and fn.id == "timer":
                findings.append(
                    (node.lineno,
                     "raw timer(...) scope — use sheeprl_tpu.obs.span so the "
                     "phase reaches the trace timeline and XLA profiles")
                )
            if (
                isinstance(fn, ast.Attribute)
                and isinstance(fn.value, ast.Name)
                and fn.value.id in clock_modules
                and fn.attr in FORBIDDEN_CLOCK_ATTRS
            ) or (isinstance(fn, ast.Name) and fn.id in clock_names):
                clock = fn.attr if isinstance(fn, ast.Attribute) else fn.id
                findings.append(
                    (node.lineno,
                     f"ad-hoc {clock}() wall-clock read — the span phases "
                     "already time this loop (and feed the histograms/flight "
                     "recorder); give a slice none of them covers its own "
                     "sheeprl_tpu.obs.span")
                )
            if (
                isinstance(fn, ast.Attribute)
                and fn.attr in FORBIDDEN_LAX_COLLECTIVES
                and _is_lax_base(fn.value)
            ):
                chokepoint = {
                    "pmean": "sheeprl_tpu.obs.dist.pmean",
                    "psum": "sheeprl_tpu.obs.dist.psum",
                    "all_gather": "sheeprl_tpu.obs.dist.instrumented_all_gather",
                }.get(fn.attr)
                findings.append(
                    (node.lineno,
                     f"raw jax.lax.{fn.attr}() collective — "
                     + (
                         f"route it through {chokepoint}"
                         if chokepoint
                         else "add a matching chokepoint to "
                         "sheeprl_tpu/obs/dist/comms.py and route through it"
                     )
                     + " so the comms attribution (obs/prof xplane collective "
                     "split) measures it")
                )
            if (
                isinstance(fn, ast.Attribute)
                and fn.attr in FORBIDDEN_FABRIC_COLLECTIVES
                and isinstance(fn.value, ast.Name)
                and fn.value.id == "fabric"
            ):
                findings.append(
                    (node.lineno,
                     f"ad-hoc fabric.{fn.attr}() host collective in an algo "
                     "entrypoint — host-level collectives belong to shared "
                     "infrastructure (plane/ckpt/obs), where their measured "
                     "comms spans are maintained (obs/dist/comms.py)")
                )
    return findings


def lint_serve_file(path: str) -> list:
    """The clock rule only, for the serving tier: ad-hoc wall-clock reads
    fragment the one timeline the trace/histogram/SLO planes share."""
    src = open(path).read()
    tree = ast.parse(src, filename=path)
    clock_modules, clock_names = _clock_aliases(tree)
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if (
            isinstance(fn, ast.Attribute)
            and isinstance(fn.value, ast.Name)
            and fn.value.id in clock_modules
            and fn.attr in FORBIDDEN_CLOCK_ATTRS
        ) or (isinstance(fn, ast.Name) and fn.id in clock_names):
            clock = fn.attr if isinstance(fn, ast.Attribute) else fn.id
            findings.append(
                (node.lineno,
                 f"ad-hoc {clock}() wall-clock read in the serving tier — "
                 "use sheeprl_tpu.obs.reqtrace.now (monotonic) or "
                 ".unix_now (wall) so request stamps stay comparable "
                 "across the trace, latency, and SLO planes")
            )
    return findings


def main() -> int:
    failures = []
    for root, _dirs, files in os.walk(ALGOS_DIR):
        for name in sorted(files):
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            for lineno, message in lint_file(path):
                failures.append(f"{os.path.relpath(path, REPO)}:{lineno}: {message}")
    for root, _dirs, files in os.walk(SERVE_DIR):
        for name in sorted(files):
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            for lineno, message in lint_serve_file(path):
                failures.append(f"{os.path.relpath(path, REPO)}:{lineno}: {message}")
    if failures:
        print("telemetry-uniformity lint FAILED:")
        for f in failures:
            print(f"  {f}")
        print(
            f"\n{len(failures)} finding(s). Algorithm entrypoints must go "
            "through the shared telemetry plumbing (sheeprl_tpu/obs/perf.py)."
        )
        return 1
    print("telemetry-uniformity lint OK (all entrypoints use the shared plumbing)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
