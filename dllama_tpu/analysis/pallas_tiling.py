"""Pallas BlockSpec tiling lint.

PALLAS-001  a ``pl.BlockSpec`` whose block-shape tuple has an int LITERAL
            in either of its last two positions that is not divisible by
            the Mosaic minimum tile — 8 for the sublane (second-to-last)
            dim, 128 for the lane (last) dim.

Why literals only: symbolic dims (``bk``, ``hd // 2``) come from the tile
planner, whose outputs the CPU lowering gate (ops.lowering) sweeps against
every real model shape — a misalignment there fails tests, not this lint.
A misaligned *literal*, by contrast, is exactly how the round-2 bench
failure shipped: it looks innocent at the call site, lowers nowhere, and no test
exercises it until a TPU does. Mosaic does accept such a block when it
spans the whole array dim ("equal-to-dim" escape), but whether it does is
a runtime fact this pass cannot see — so a deliberate whole-array literal
must carry (rule id spelled out here so this docstring is not itself
parsed as a suppression)::

    # dllama: allow[PALLAS-nnn] reason=whole-array dim (proven: tests/test_lowering.py sweep)

which keeps every exception audited (SUP-001) and auto-expiring (SUP-002)
and, per the reason convention above, pointing at the sweep case that
proves it.
"""

from __future__ import annotations

import ast

from .core import Finding, SourceFile

_SUBLANE, _LANE = 8, 128


def _block_shape(call: ast.Call):
    """The block-shape tuple of a BlockSpec call, or None.

    Accepts the positional form ``BlockSpec((..), index_map)`` and the
    keyword form ``BlockSpec(block_shape=(..))``; memory-space-only specs
    (``BlockSpec(memory_space=pl.ANY)``) have no shape to check.
    """
    if call.args and isinstance(call.args[0], ast.Tuple):
        return call.args[0]
    for kw in call.keywords:
        if kw.arg == "block_shape" and isinstance(kw.value, ast.Tuple):
            return kw.value
    return None


def check_blockspecs(src: SourceFile):
    findings = []
    for node in ast.walk(src.tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        name = func.attr if isinstance(func, ast.Attribute) else (
            func.id if isinstance(func, ast.Name) else "")
        if name != "BlockSpec":
            continue
        shape = _block_shape(node)
        if shape is None or not shape.elts:
            continue
        # (dim, minimum, axis-name) for the last two positions; a 1-D
        # block only has a lane dim
        tail = [(shape.elts[-1], _LANE, "lane")]
        if len(shape.elts) >= 2:
            tail.append((shape.elts[-2], _SUBLANE, "sublane"))
        for elt, mult, axis in tail:
            if not (isinstance(elt, ast.Constant)
                    and isinstance(elt.value, int)
                    and not isinstance(elt.value, bool)):
                continue  # planner-derived symbolic dim: the sweep's job
            if elt.value % mult == 0:
                continue
            findings.append(Finding(
                "PALLAS-001", src.rel, elt.lineno,
                f"literal {axis} block dim {elt.value} is not divisible by "
                f"{mult} — lowers under Mosaic only if it equals the array "
                f"dim; if so, suppress with a reason naming the sweep case "
                f"that proves it"))
    return findings
