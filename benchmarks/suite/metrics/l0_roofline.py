"""l0_roofline: share of the chip's roofline in the ℓ0 spans: the least
time the chip could take for the algorithm's work of the traced fits
(``work.l0_work``, at the published peaks) over the device's busy time
inside the benchmark's ``bench.l0`` spans (device trace)."""
from benchmarks.suite import work


def read(run):
    if run.trace is None or not run.fits:
        return None
    busy = run.trace.phase_busy_s["l0"]
    if busy <= 0:
        return None
    ops = nbytes = 0.0
    for f in run.fits:
        o, b = work.l0_work(f.shape)
        ops, nbytes = ops + o, nbytes + b
    seconds, _bound = work.roofline_seconds(ops, nbytes, run.peak)
    return 100.0 * seconds / busy
