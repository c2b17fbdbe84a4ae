"""l0_s: seconds of the ℓ0 search per fit, the solver's own ``timings["l0"]``
(host clock) averaged over the traced window's fits."""


def read(run):
    done = [f.timings["l0"] for f in run.fits]
    return sum(done) / len(done) if done else None
