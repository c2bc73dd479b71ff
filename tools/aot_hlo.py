#!/usr/bin/env python3
"""Compile a function for a described ``v5e`` topology, with no chip, and print
where the compiler reckons its time goes.

    JAX_PLATFORMS=cpu python tools/aot_hlo.py sheeprl_tpu.kernels.delta_rule:step \\
        f32[8,32,128,128] f32[8,32,128] f32[8,32,128] f32[8,32,128] f32[8,32] f32[8,32] --top 10

One row an ``op_name`` (the program's ``jax.named_scope`` path): the sum of
its fusions' ``estimated_cycles`` in the optimized HLO, a loop's body counted
once a trip (the constant its condition compares with). The compiler's
estimate, not a time: good for shares and for finding a scope's operations,
nothing to quote as a device number (ISSUE 33 found it a third high). A Pallas
kernel (``tpu_custom_call``) carries no estimate and reads 0. From code:
``compiled = compile_for(fn, *specs)``, then ``rows(compiled.as_text())`` and
``compiled.memory_analysis()``.
"""

from __future__ import annotations

import argparse
import importlib
import os
import re
import sys
from collections import defaultdict

os.environ.setdefault("TPU_LOG_DIR", "disabled")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\{\s*$")
_CYCLES = re.compile(r'"estimated_cycles":"(\d+)"')
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WHILE = re.compile(r"\bwhile\(.*condition=%([^\s,]+), body=%([^\s,]+)")
_CALLED = re.compile(r"\b(?:to_apply|true_computation|false_computation)=%([^\s,}]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_CONSTANT = re.compile(r"\bs32\[\][^=]*constant\((\d+)\)")
_SPEC = re.compile(r"^(\w+)\[([\d,]*)\]$")


def compile_for(fn, *specs, topology: str = "v5e:2x2"):
    """``fn`` compiled for the first chip of ``topology``; ``specs`` are pytrees
    of ``jax.ShapeDtypeStruct`` (a sharding of theirs is replaced)."""
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    chip = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name=topology).devices[0])
    specs = jax.tree_util.tree_map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), specs)
    return jax.jit(fn).lower(*specs).compile()


def rows(hlo: str) -> list:
    """``[(cycles, operations, op_name)]`` of an optimized HLO module, most
    cycles first, loop bodies weighted by their trip counts."""
    computations, entry, name = defaultdict(list), None, None
    for line in hlo.splitlines():
        start = _COMPUTATION.match(line)
        if start:
            name = start.group(1)
            entry = name if line.startswith("ENTRY") else entry
        elif name and line.strip() != "}":
            computations[name].append(line)
    found = defaultdict(lambda: [0, 0])

    def walk(computation: str, weight: int) -> None:
        for line in computations[computation]:
            loop = _WHILE.search(line)
            if loop:
                trips = [int(n) for n in _CONSTANT.findall("\n".join(computations[loop.group(1)]))]
                walk(loop.group(2), weight * (max(trips) if trips else 1))
                continue
            branches = _BRANCHES.search(line)
            for called in _CALLED.findall(line) + (re.findall(r"%([^\s,]+)", branches.group(1)) if branches else []):
                walk(called, weight)
            cycles = _CYCLES.search(line)
            if cycles:
                op = _OP_NAME.search(line)
                row = found[op.group(1) if op else ""]
                row[0] += weight * int(cycles.group(1))
                row[1] += weight

    walk(entry, 1)
    return sorted(((c, n, op) for op, (c, n) in found.items()), reverse=True)


def main() -> None:
    import jax
    import jax.numpy as jnp

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("function", help="module:attribute, called with one array an argument")
    parser.add_argument("specs", nargs="+", help="dtype[shape], as f32[8,1024,32,128]")
    parser.add_argument("--top", type=int, default=30)
    parser.add_argument("--match", default="", help="keep op_names that hold this")
    parser.add_argument("--topology", default="v5e:2x2")
    args = parser.parse_args()
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    module, attribute = args.function.split(":")
    dtypes = {"f32": jnp.float32, "bf16": jnp.bfloat16, "s32": jnp.int32}
    specs = [jax.ShapeDtypeStruct(tuple(int(n) for n in shape.split(",") if n), dtypes[dtype])
             for dtype, shape in (_SPEC.match(s).groups() for s in args.specs)]
    compiled = compile_for(getattr(importlib.import_module(module), attribute), *specs, topology=args.topology)
    kept = [r for r in rows(compiled.as_text()) if args.match in r[2]]
    print(f"{sum(r[0] for r in kept):>14,d} cycles reckoned in {len(kept)} op_names; {compiled.memory_analysis()}")
    for cycles, operations, op in kept[: args.top]:
        print(f"{cycles:>14,d} {operations:>6d}  {op}")


if __name__ == "__main__":
    main()
