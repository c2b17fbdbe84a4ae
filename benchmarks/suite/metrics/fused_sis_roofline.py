"""fused_sis_roofline: share of the roofline of every chip a distributed
fit used, in the SIS spans: the least time one chip could take for the
algorithm's work of the traced fits (``work.sis_work``, at the published
peaks of one chip) over the devices the fit sharded over (the program's
own counter ``stats["sharded"]["devices"]``, ``fit_stats``) times the
device busy time inside the benchmark's ``bench.sis`` spans, which the
trace gives as a mean over those chips.  None where the program keeps no
such counter."""
from benchmarks.suite import fit_stats, work


def read(run):
    if run.trace is None or not run.fits:
        return None
    devices = {s.get("sharded", {}).get("devices")
               for s in fit_stats.window_stats(run) or [{}]}
    if len(devices) != 1 or None in devices:
        return None
    busy = run.trace.phase_busy_s["sis"] * devices.pop()
    if busy <= 0:
        return None
    ops = nbytes = 0.0
    for f in run.fits:
        o, b = work.sis_work(f.shape)
        ops, nbytes = ops + o, nbytes + b
    seconds, _bound = work.roofline_seconds(ops, nbytes, run.peak)
    return 100.0 * seconds / busy
