"""l0_wait_s: seconds per fit that the ℓ0 merge loop waits on the block
workers (device scoring, or a block not yet dispatched), the program's own
``timings["l0_wait"]`` (spans ``sisso.l0.wait``, host clock) averaged over
the traced window's fits; None where a fit lacks it."""


def read(run):
    done = [f.timings.get("l0_wait") for f in run.fits]
    if not done or None in done:
        return None
    return sum(done) / len(done)
