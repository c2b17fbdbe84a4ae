"""fit_s: time to solution of one campaign (FC + SIS + ℓ0), the window's
completed fits' summed wall time over their count (host clock)."""


def read(run):
    done = [f.seconds for f in run.fits]
    return sum(done) / len(done) if done else None
