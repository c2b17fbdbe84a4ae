"""fc_s: seconds of feature creation per fit, the solver's own ``timings["fc"]``
(host clock) averaged over the traced window's fits."""


def read(run):
    done = [f.timings["fc"] for f in run.fits]
    return sum(done) / len(done) if done else None
