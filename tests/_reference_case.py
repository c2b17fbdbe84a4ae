"""A small thermal-like SISSO campaign, fitted by the program and by the
benchmark's plain reference (``benchmarks/suite/reference.py``), which
imports nothing of the program: five primaries with units in two tasks,
the thermal operator set, rung 2 with the last rung on the fly."""
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmarks.suite import compare, harness, reference, tracing  # noqa: E402
from benchmarks.suite.data import make_data  # noqa: E402

CASE = {
    "data_seed": 3,
    "samples_per_task": [30, 26],
    "unit_basis": ["kg", "m", "s", "K"],
    "primaries": [{
        "names": ["f0", "f1", "f2", "f3", "f4"],
        "dist": "uniform", "low": 0.5, "high": 5.0,
        "units": [{"m": 3}, {"kg": 1}, {"K": 1}, {}, {"m": 3}],
    }],
    "law": {"terms": [{"expr": "(f0 * f4)", "coef": [3.0, 2.2]},
                      {"expr": "(f2)^2", "coef": [-0.8, -0.5]}],
            "intercept": [1.0, -0.5], "noise": 0.01},
    "op_names": ["add", "sub", "mul", "div", "abs_diff", "sqrt", "cbrt",
                 "sq", "cb", "inv", "log", "exp", "neg_exp", "abs"],
    "max_rung": 2, "n_dim": 3, "n_sis": 12, "n_residual": 5,
    "l_bound": 1e-5, "u_bound": 1e8, "precision": "fp64",
    "on_the_fly_last_rung": True, "max_pairs_per_op": None,
    "l0_block": 4096, "backend": "jnp",
}


def data(seed: int = 1):
    return make_data(CASE, seed)


def reference_campaign(settings: dict, d):
    return reference.run(d.x, d.y, d.names, d.units, d.task_slices,
                         {k: settings[k] for k in harness.REFERENCE_KEYS})


def fit(settings: dict, d):
    """One fit through the public estimator, as a benchmark run makes it:
    its ``harness.FitRecord`` (the features each dimension selected, the
    models, the timings) and the fit's own record of spans and counters
    (``repro.runtime.trace.FitTrace``)."""
    from repro.runtime.trace import recent_fits

    inst = tracing.Instrument(annotate=False)
    with inst.installed():
        record = harness.fit_once(settings, d, inst,
                                  harness.program_units(d))
    return record, recent_fits()[-1]


def readings(settings: dict, d, answers, ref=None) -> dict:
    """``fc_gap``, ``sis_gap`` and ``l0_gap`` of a fit's answers against
    the reference campaign (``compare.Judge``)."""
    ref = ref or reference_campaign(settings, d)
    return compare.Judge(ref, d.x, d.y, d.names,
                         d.task_slices).readings(answers)
