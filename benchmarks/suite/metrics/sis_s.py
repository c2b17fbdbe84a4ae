"""sis_s: seconds of SIS screening per fit, the solver's own ``timings["sis"]``
(host clock) averaged over the traced window's fits."""


def read(run):
    done = [f.timings["sis"] for f in run.fits]
    return sum(done) / len(done) if done else None
