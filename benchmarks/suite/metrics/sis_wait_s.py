"""sis_wait_s: seconds per fit that the SIS screen waits on its block
workers for deferred (on-the-fly) candidate blocks, the program's own
``timings["sis_wait"]`` (spans ``sisso.sis.wait``, host clock) averaged
over the traced window's fits; None where a fit lacks it."""


def read(run):
    done = [f.timings.get("sis_wait") for f in run.fits]
    if not done or None in done:
        return None
    return sum(done) / len(done)
