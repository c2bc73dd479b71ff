"""The arithmetic of ``correct``: gaps by the worst leaf, and the verdict."""

import math

import pytest

from benchmarks import check


def test_worst_leaf_gap_is_a_gap_of_norms_against_leaf_or_median():
    reference = {"a": 10.0, "b": 1.0, "c": 1e-9, "d": 2.0, "e": 4.0}
    program = {"a": 10.5, "b": 1.0, "c": 1e-3, "d": 2.0, "e": 4.0}
    # median leaf is 2.0: leaf c's gap of 1e-3 is held against 2.0, not against 1e-9
    gap, leaf = check.worst_leaf_gap(program, reference)
    assert leaf == "a" and gap == pytest.approx(0.05)
    gap, leaf = check.worst_leaf_gap({**program, "a": 10.0}, reference)
    assert leaf == "c" and gap == pytest.approx(1e-3 / 2.0)
    assert check.worst_leaf_gap(program, reference, leave_out={"a"})[1] == "c"


def test_an_unmoved_or_doubled_leaf_reads_about_one_and_nan_is_worst():
    reference = {"a": 3.0, "b": 3.0, "c": 3.0}
    assert check.worst_leaf_gap({"a": 0.0, "b": 3.0, "c": 3.0}, reference)[0] == pytest.approx(1.0)
    assert check.worst_leaf_gap({"a": 6.0, "b": 3.0, "c": 3.0}, reference)[0] == pytest.approx(1.0)
    gap, leaf = check.worst_leaf_gap({"a": 3.0, "b": math.nan, "c": 3.0}, reference)
    assert leaf == "b" and math.isnan(gap)


def test_dead_leaves_are_found_by_the_reference_gradient_not_by_name():
    grads = {"w1": 1.0, "w2": 2.0, "w3": 0.5, "key_bias": 1e-9, "w4": 1.5}
    assert check.dead_leaves(grads) == {"key_bias"}


@pytest.mark.parametrize(
    "numbers, limits, correct",
    [
        ({"x": 0.1, "rows": 0}, {"x": 0.2, "rows": 0}, True),
        ({"x": 0.3, "rows": 0}, {"x": 0.2, "rows": 0}, False),
        ({"x": 0.1, "rows": 1}, {"x": 0.2, "rows": 0}, False),
        ({"x": math.nan, "rows": 0}, {"x": 0.2, "rows": 0}, False),
        ({"rows": 0}, {"x": 0.2, "rows": 0}, False),  # a comparison that did not run
        ({"x": 0.1, "rows": 0, "y": 0.0}, {"x": 0.2, "rows": 0}, False),  # a number with no limit
    ],
)
def test_verdict(numbers, limits, correct):
    ok, rows = check.verdict(numbers, limits)
    assert ok is correct
    assert [r[0] for r in rows] == sorted(set(numbers) | set(limits))


def test_a_number_named_not_compared_is_read_and_held_to_nothing():
    ok, rows = check.verdict({"x": 0.1, "g": 9.0}, {"x": 0.2}, not_compared=["g"])
    assert ok is True and rows == [["x", 0.1, 0.2], ["g", 9.0, None]]
    assert check.verdict({"x": 0.3, "g": 0.0}, {"x": 0.2}, not_compared=["g"])[0] is False
    with pytest.raises(ValueError):
        check.verdict({"g": 0.0}, {"g": 0.2}, not_compared=["g"])
