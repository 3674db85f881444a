"""The comparison that decides ``correct`` for a training cell.

The program's first steps (losses, the norm of each leaf's first gradient as
the optimizer got it, the norm of each leaf's change over the steps) against
the plain reference's.  Norms are compared leaf by leaf by the GAP OF NORMS,
never the norm of a difference, measured against the reference's norm of
that leaf or of the median leaf, whichever is larger (some gradients are all
but zero); the number compared is the worst leaf's.

Norms do not see rounding: noise of relative size r moves a norm by r*r/2,
so a reference in int8 or fp8 reads like bfloat16 on every number above.
``grad_diff`` is the number that separates them: the size of the DIFFERENCE
between the program's first gradient and the reference's, over an evenly
spaced sample of each leaf's elements, as a share of the reference's; the
median leaf's, which is steady from seed to seed.  Where depth amplifies
rounding on the way back (ResNet's BatchNorms: PERF.md) the median leaf
is too noisy to separate them, and the same difference is read where it is
not amplified: ``grad_diff_head``, of the leaf with the largest gradient (the
classifier's weights).

Leaves whose reference gradient is under a thousandth of the median leaf's
(a key projection's bias under softmax, a convolution's bias under
BatchNorm) have no gradient but round-off, and move by round-off alone:
they are left out of the three numbers on gradients and changes, by that
rule and not by name.
"""
from __future__ import annotations

import math
import statistics

DEAD_GRADIENT = 1e-3  # of the median leaf's gradient norm


def _gaps(got, want, names):
    floor = statistics.median(want[n] for n in names)  # of the live leaves
    return {n: abs(got[n] - want[n]) / max(want[n], floor, 1e-30)
            for n in names}


def _worst(got, want, names):
    worst, where = 0.0, None
    for n, gap in _gaps(got, want, names).items():
        if not gap <= worst:  # a NaN is the worst there is
            worst, where = gap, n
    return worst, where


def readings(program, reference):
    """Every number this module can read, {name: value} in a fixed order, and
    the leaf each worst-leaf number was read on.  ``program``/``reference``:
    {"losses", "grad_norms", "grad_samples", "delta_norms"}."""
    numbers, leaves = {}, {}
    for i, (got, want) in enumerate(zip(program["losses"],
                                        reference["losses"])):
        numbers["loss_gap_%d" % (i + 1)] = abs(got - want) / abs(want)
    trainable = sorted(reference["grad_norms"])
    if sorted(program["grad_norms"]) != trainable:
        raise ValueError("program and reference disagree on the leaves")
    moved = [v for v in reference["grad_norms"].values() if v > 0]
    floor = DEAD_GRADIENT * statistics.median(moved)
    alive = [n for n in trainable if reference["grad_norms"][n] > floor]
    numbers["grad_norm_gap"], leaves["grad_norm_gap"] = _worst(
        program["grad_norms"], reference["grad_norms"], alive)
    numbers["grad_norm_gap_median"] = statistics.median(_gaps(
        program["grad_norms"], reference["grad_norms"], alive).values())
    numbers["delta_norm_gap"], leaves["delta_norm_gap"] = _worst(
        program["delta_norms"], reference["delta_norms"], alive)
    diffs = {}
    for n in alive:
        got, want = program["grad_samples"][n], reference["grad_samples"][n]
        diffs[n] = float(((got - want) ** 2).sum() ** 0.5
                         / max((want ** 2).sum() ** 0.5, 1e-30))
    numbers["grad_diff"] = statistics.median(diffs.values())
    head = max(alive, key=reference["grad_norms"].get)
    numbers["grad_diff_head"], leaves["grad_diff_head"] = diffs[head], head
    stats = sorted(set(reference["delta_norms"]) - set(trainable))
    if stats:  # leaves no gradient moves: BatchNorm's running statistics
        numbers["stats_delta_gap"], leaves["stats_delta_gap"] = _worst(
            program["delta_norms"], reference["delta_norms"], stats)
    return numbers, leaves


def compare(program, reference, limits):
    """(numbers, leaves): numbers is {name: {"value", "limit"}} for the
    numbers that ``limits`` names; one over its limit, or not finite, fails.
    A number the cell's limits do not name is read and shown under
    ``leaves["not_compared"]``, and not compared (PERF.md says which, why)."""
    numbers, leaves = readings(program, reference)
    unknown = sorted(set(limits) - set(numbers) - {"_note"})
    if unknown:
        raise ValueError("limits name numbers that are not read: %s"
                         % unknown)
    out = {name: {"value": value, "limit": limits[name]}
           for name, value in numbers.items() if name in limits}
    leaves["not_compared"] = {name: value for name, value in numbers.items()
                              if name not in limits}
    return out, leaves


def passed(numbers):
    return all(math.isfinite(n["value"]) and n["value"] <= n["limit"]
               for n in numbers.values())
