"""How ``correct`` is decided: numbers compared, each beside its own limit.

The numbers and the limits they are held to are named in the configuration
file (``check.limits.<driver>``); PERF.md gives the readings each limit was
set from.  Every run prints every number with its limit.
"""

from __future__ import annotations

import json
import math
import statistics
import sys


def worst_leaf_gap(program: dict, reference: dict, skip: set = frozenset()) -> tuple[float, str]:
    """The largest gap between the program's norm and the reference's over
    the leaves, each measured against the reference's norm of that leaf or of
    the median leaf, whichever is larger (some gradients are all but zero)."""
    if set(program) != set(reference):
        missing = sorted(map(str, set(reference) ^ set(program)))[:5]
        raise RuntimeError(f"program and reference leaves differ: {missing}")
    floor = statistics.median(reference.values())
    worst, where = 0.0, ""
    for key, ref in reference.items():
        if key in skip:
            continue
        gap = abs(program[key] - ref) / max(ref, floor, 1e-30)
        if not math.isfinite(gap):
            return float("inf"), str(key)
        if gap > worst:
            worst, where = gap, str(key)
    return worst, where


def median_leaf_rel_diff(program: dict, reference: dict, skip: set = frozenset()) -> float:
    """Median over the leaves of |program - reference| / |reference| on each
    leaf's sampled elements: first-order in the arithmetic's error (a gap of
    norms is second-order), and steady from seed to seed (a median)."""
    vals = []
    for key, ref in reference.items():
        norm = float((ref ** 2).sum() ** 0.5)
        if key in skip or norm == 0.0:
            continue
        vals.append(float(((program[key] - ref) ** 2).sum() ** 0.5) / norm)
    return statistics.median(vals) if vals else float("inf")


def rounding_only_leaves(first_grad_reference: dict, share: float = 1e-4) -> set:
    """Leaves whose true gradient is zero (an attention key bias: the softmax
    is blind to it), so that the reference's gradient there is rounding alone
    (under ``share`` of the median leaf's).  Adam scales whatever it gets to
    steps of the learning rate, noise included, so the CHANGE of such a leaf
    says nothing about the step and is left out of that one comparison."""
    floor = share * statistics.median(first_grad_reference.values())
    return {k for k, v in first_grad_reference.items() if v < floor}


def judge(numbers: dict[str, float], limits: dict[str, float], notes: dict[str, str] | None = None) -> bool:
    """Print each number beside its limit; True when all are inside.  A
    number with no limit, or a limit with no number, is a failure: a check
    that silently compares less is not a check."""
    ok = True
    for name in sorted(set(numbers) | set(limits)):
        value, limit = numbers.get(name), limits.get(name)
        inside = (
            value is not None and limit is not None and math.isfinite(value) and value <= limit
        )
        ok &= inside
        line = {"check": name, "value": value, "limit": limit, "ok": inside}
        if notes and name in notes:
            line["at"] = notes[name]
        sys.stdout.write(json.dumps(line) + "\n")
    sys.stdout.flush()
    return ok


def control_caught(numbers: dict[str, float], limits: dict[str, float]) -> bool:
    """Print the control's numbers beside the same limits; True when the
    control comes out as NOT correct (it has to fail one number, not each)."""
    caught = False
    for name in sorted(numbers):
        value, limit = numbers[name], limits.get(name)
        over = limit is None or not math.isfinite(value) or value > limit
        caught |= over
        sys.stdout.write(json.dumps({"control": name, "value": value, "limit": limit, "caught": over}) + "\n")
    sys.stdout.flush()
    return caught
