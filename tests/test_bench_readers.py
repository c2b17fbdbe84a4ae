"""The benchmark's readers of the program's spans and counters
(benchmarks/suite/metrics), on a synthetic run."""
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from benchmarks.suite import harness  # noqa: E402
from repro.runtime import trace  # noqa: E402

FALLBACK = "exact fp64 (window not certified)"
KERNEL = "Gram-gather kernel"


class Counted(tuple):
    """A fit run through the program's record that counted these
    ``(key, n)``; the readers find its counters among the recent fits."""


def _fit(spec):
    if not isinstance(spec, Counted):
        return harness.FitRecord(seconds=1.0, timings=spec, shape={},
                                 answers=None)
    with trace.collecting() as rec:
        for key, n in spec:
            trace.count(key, n)
    return harness.FitRecord(seconds=1.0,
                             timings={"fit": rec.seconds("sisso.fit")},
                             shape={}, answers=None)


def _programs(*lowered):
    return Counted(((("programs", f"sisso.s{i}", "lowered"), n)
                    for i, n in enumerate(lowered)))


def _paths(by_width):
    return Counted(((("l0_paths", w, path), n)
                    for w, paths in by_width.items()
                    for path, n in paths.items()))


CASES = [
    ("descriptor_s", [{"descriptor": 0.5}, {"descriptor": 0.7}], 0.6),
    ("descriptor_s", [{"descriptor": 0.5}, {"fc": 1.0}], None),
    ("descriptor_s", [], None),
    ("l0_wait_s", [{"l0_wait": 2.0}, {"l0_wait": 4.0}], 3.0),
    ("l0_wait_s", [{"l0": 2.0}], None),
    ("programs_lowered", [_programs(3, 4), _programs(9)], 8.0),
    # a program that keeps no record of its fits reports no "fit" timing
    ("programs_lowered", [{"fc": 1.0}], None),
    ("programs_lowered", [_programs()], 0.0),
    ("l0_fp64_block_share", [
        _paths({"3": {KERNEL: 6, FALLBACK: 2},
                "2": {"closed-form pairs": 1}}),
        _paths({"3": {KERNEL: 8}})], 12.5),
    ("l0_fp64_block_share", [
        _paths({"1": {"jnp": 1}, "2": {"closed-form pairs": 3}})], None),
    ("l0_fp64_block_share", [Counted()], None),
    ("l0_fp64_block_share", [_paths({"4": {FALLBACK: 1}})], 100.0),
    # the newest record is not the window's fit
    ("programs_lowered", [_programs(3), {"fit": -1.0}], None),
    ("l0_fp64_block_share", [{"fit": -1.0}, _paths({"3": {KERNEL: 1}})],
     None),
]


@pytest.mark.parametrize(
    "metric,fits,want", CASES,
    ids=[f"{m}-{i}" for i, (m, _, _) in enumerate(CASES)])
def test_reader(metric, fits, want):
    run = harness.Run(setup_s=1.0, fits=[_fit(f) for f in fits], trace=None,
                      peak={})
    got = harness.load_reader(metric)(run)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)
