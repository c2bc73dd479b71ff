"""The readers PR 26 added: the program's own spans per burst, and the train
module's device time by ``dv3/<part>`` scope (``benchmarks/scopes.py``)."""

import json
import os
import shutil

import pytest

import bench_tiny
from benchmarks import reduce, run, scopes
from benchmarks.manifest import Manifest

#: recorded on a v5e (PR 26): three gradient steps of a toy ``local_burst``
#: whose loss has ``dv3/encoder``, a ``dv3/rssm`` scan and ``dv3/heads``, with
#: ``dv3/optimizer`` around the update, under jax.grad
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "scoped_v5e.xplane.pb")
PROGRAM = 7
MODULE = [(f"jit_local_burst({PROGRAM})", 0.0, 10.0)]


def stack(scope):
    return f"jit(local_burst)/while/body/{scope}/dot_general:"


# -- the reducer on hand-made events ------------------------------------------------


def test_forward_and_backward_of_a_part_are_summed():
    ops = [("fusion.1", 0.0, 2.0), ("fusion.2", 2.0, 5.0), ("fusion.3", 5.0, 6.0)]
    names = {(PROGRAM, "fusion.1"): stack("jvp(dv3/heads)"), (PROGRAM, "fusion.2"): stack("transpose(jvp(dv3/heads))"),
             (PROGRAM, "fusion.3"): stack("dv3/optimizer")}
    parts = scopes.part_seconds(ops, MODULE, names)
    assert parts == pytest.approx({"heads": 5.0, "optimizer": 1.0, "unscoped": 0.0, "module": 10.0})


def test_a_while_has_its_body_taken_out_and_keeps_its_own_scope():
    ops = [("while.1", 0.0, 8.0), ("fusion.1", 1.0, 3.0), ("fusion.2", 3.0, 7.0), ("copy.1", 8.0, 9.0)]
    names = {(PROGRAM, "while.1"): stack("jvp(dv3/rssm)"), (PROGRAM, "fusion.1"): stack("jvp(dv3/rssm)/while/body"),
             (PROGRAM, "fusion.2"): stack("jvp(dv3/encoder)")}
    parts = scopes.part_seconds(ops, MODULE, names)
    # the while's own 2 s (8 less its body's 6) and fusion.1 are the scan's
    assert parts == pytest.approx({"rssm": 4.0, "encoder": 4.0, "unscoped": 1.0, "module": 10.0})


def test_an_unscoped_fusion_counts_as_unscoped_and_other_programs_do_not_count():
    ops = [("fusion.1", 0.0, 1.0), ("fusion.9", 1.0, 4.0), ("fusion.1", 12.0, 13.0)]
    names = {(PROGRAM, "fusion.1"): stack("jvp(dv3/behavior)"), (PROGRAM + 1, "fusion.9"): stack("jvp(dv3/rssm)")}
    parts = scopes.part_seconds(ops, MODULE, names)
    # fusion.9 has a scope in another program only; the last event ran outside the module
    assert parts == pytest.approx({"behavior": 1.0, "unscoped": 3.0, "module": 10.0})


def test_an_instant_is_counted_once_where_operations_overlap():
    """On the chip an operation's event often ends after the next has begun:
    the overlap goes to the later one, and the total is the union."""
    events = [("while.1", 0.0, 10.0), ("fusion.1", 1.0, 4.0), ("fusion.2", 3.5, 6.0), ("fusion.1", 7.0, 8.0),
              ("copy.1", 11.0, 12.0), ("fusion.3", 20.0, 30.0), ("fusion.4", 29.0, 32.0), ("fusion.5", 31.0, 31.5)]
    mine = scopes.exclusive_seconds(events)
    assert mine == pytest.approx({"while.1": 4.0, "fusion.1": 3.5, "fusion.2": 2.5, "copy.1": 1.0,
                                  "fusion.3": 9.0, "fusion.4": 2.5, "fusion.5": 0.5})
    assert sum(mine.values()) == pytest.approx(reduce.union_seconds([(s, e) for _n, s, e in events])[0])
    # without overlaps it is reduce.self_seconds
    nested = [("while", 0.0, 10.0), ("fusion.1", 1.0, 4.0), ("fusion.2", 4.0, 6.0), ("fusion.1", 7.0, 8.0), ("copy", 11.0, 12.0)]
    assert scopes.exclusive_seconds(nested) == pytest.approx(reduce.self_seconds(nested))


def test_no_scoped_operation_reads_as_none():
    ops = [("fusion.1", 0.0, 1.0)]
    assert scopes.part_seconds(ops, MODULE, {}) is None
    assert scopes.part_seconds(ops, MODULE, {(PROGRAM, "fusion.1"): "jit(local_burst)/while/body/add:"}) is None
    assert scopes.part_seconds(ops, [], {(PROGRAM, "fusion.1"): stack("dv3/rssm")}) is None


@pytest.mark.parametrize("scope, part", [
    ("jit(local_burst)/while/body/jvp(dv3/rssm)/while/body/closed_call/dot_general:", "rssm"),
    ("jit(local_burst)/while/body/transpose(jvp(dv3/heads))/dot_general:", "heads"),
    ("jit(local_burst)/while/body/dv3/optimizer/mul:", "optimizer"),
    ("jit(local_burst)/while/body/add:", None),
    (None, None),
])
def test_the_part_is_the_first_dv3_scope(scope, part):
    assert scopes.part_of(scope) == part


# -- on a trace recorded on the chip ------------------------------------------------


def test_the_scopes_of_a_recorded_trace_are_read_and_cover_the_module():
    names = scopes.op_scopes(RECORDED)
    assert list(names) == ["/device:TPU:0"]
    names = names["/device:TPU:0"]
    programs = {program for program, _op in names}
    assert len(programs) == 1
    (program,) = programs
    assert names[(program, "convolution_tanh_fusion.2")] == "jit(local_burst)/while/body/jvp(dv3/encoder)/dot_general:"
    assert names[(program, "fusion.56")] == "jit(local_burst)/while/body/transpose(jvp(dv3/heads))/dot_general:"
    lines = reduce.read_planes(RECORDED)["/device:TPU:0"]
    (module,) = lines["XLA Modules"]
    assert module[0] == f"jit_local_burst({program})"
    parts = scopes.part_seconds(lines["XLA Ops"], lines["XLA Modules"], names)
    assert set(parts) == {"encoder", "rssm", "heads", "optimizer", "unscoped", "module"}
    # the operations cover the module's device time, and no instant counts twice
    assert sum(v for k, v in parts.items() if k != "module") == pytest.approx(parts["module"], rel=0.01)
    assert sum(v for k, v in parts.items() if k != "module") <= parts["module"]
    assert parts["rssm"] > parts["heads"] > parts["encoder"] > parts["unscoped"] > parts["optimizer"] > 0
    assert parts["unscoped"] / parts["module"] < 0.1


# -- through a whole traced run at tiny widths ----------------------------------------

SPAN_METRICS = ("train.synced_ms_per_burst.learn", "train.dispatch_ms_per_burst.learn",
                "stage.sample_ms_per_burst.learn", "store.add_ms_p50.learn")
LEFT_OUT = ("publish.refresh_ms_per_burst.learn", "publish.mib_per_burst.learn",
            "train.rssm_ms_per_grad_step.learn", "train.unscoped_pct.learn")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The tiny benchmark plus this PR's metric files and manifest entries."""
    root = str(tmp_path_factory.mktemp("bench"))
    manifest, cell = bench_tiny.write_tiny_benchmark(root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        data = json.load(f)
    for name in SPAN_METRICS + LEFT_OUT:
        shutil.copy(os.path.join(bench_tiny.BENCH, "metrics", name + ".py"), os.path.join(root, "bench", "metrics"))
        data["per_layer"].append({"name": name, "unit": "ms", "better": "lower", "source": "program_span",
                                  "layer": "test", "moves": "replay_steps_per_s"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    manifest = Manifest(os.path.join(root, "BENCHMARK.json"), root=os.path.join(root, "bench"))
    return run.run_cell(cell, 11, 0.5, True, manifest=manifest, require_chip=False, accelerator="cpu")


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_a_traced_run_reads_the_programs_own_spans(traced, name):
    assert traced["correct"] is True, traced["checks"]
    assert traced["metrics"][name]["value"] > 0


def test_dispatch_and_sync_lie_inside_the_train_span(traced):
    metrics = traced["metrics"]
    assert metrics["train.dispatch_ms_per_burst.learn"]["value"] < metrics["train.synced_ms_per_burst.learn"]["value"]
    assert metrics["train.synced_ms_per_burst.learn"]["value"] <= metrics["train.host_ms_per_burst.learn"]["value"]


@pytest.mark.parametrize("name", LEFT_OUT)
def test_a_reader_with_nothing_to_read_is_left_out(traced, name):
    """No mirror on the CPU (the program acts on the device's own vector), and
    no device plane in a CPU trace: the readers return ``None``."""
    assert name not in traced["metrics"]
