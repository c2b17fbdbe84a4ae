"""descriptor_s: seconds per fit that the estimator spends compiling every
model's descriptor and replaying it on the training data, the program's own
``timings["descriptor"]`` (span ``sisso.descriptor``, host clock) averaged
over the traced window's fits; None where a fit lacks it."""


def read(run):
    done = [f.timings.get("descriptor") for f in run.fits]
    if not done or None in done:
        return None
    return sum(done) / len(done)
