"""fc_enumerate_s: seconds per fit that feature creation spends enumerating
each operator's child sets on the host (unit, domain and structural
rules), the program's own ``timings["fc_enumerate"]`` (spans
``sisso.fc.enumerate``, host clock) averaged over the traced window's
fits; None where a fit lacks it."""


def read(run):
    done = [f.timings.get("fc_enumerate") for f in run.fits]
    if not done or None in done:
        return None
    return sum(done) / len(done)
