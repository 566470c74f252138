"""The control: the configuration's reference, one precision step below
what the configuration states, put in the program's place, reads above the
sound program on the number the check compares, seed by seed, on the same
requests or candidates.  Here at tiny widths on the CPU, where the margin
is smaller than at the cells' own sizes; ``calibrate.py`` takes the same
readings on the chip, where the limits come from."""

import statistics

import jax

import tiny

SEEDS = (1, 2, 3)


def _readings(cell, seconds):
    return [cell.entry.calibrate(cell, s, seconds, jax.devices()[:1])
            for s in SEEDS]


def test_int8_control_reads_above_the_served_model():
    rows = _readings(tiny.serving_cell(), 2.0)
    assert all(r["control"] > r["program"] for r in rows), rows
    assert statistics.median(r["control"] / max(r["program"], 1e-9)
                             for r in rows) >= 3


def test_bfloat16_control_reads_above_the_search():
    rows = _readings(tiny.search_cell(), 3.0)
    assert all(r["control"] > r["program"] for r in rows), rows
