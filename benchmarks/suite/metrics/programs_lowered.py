"""programs_lowered: programs lowered to MLIR per fit, the program's own
counter ``stats["programs"][span]["lowered"]`` summed over its spans and
averaged over the traced window's fits (``fit_stats``); each is a lowering
that a warm fit repeats.  None where the program keeps no such counter."""
from benchmarks.suite import fit_stats


def read(run):
    done = fit_stats.window_stats(run)
    if not done:
        return None
    return sum(sum(k["lowered"] for k in s["programs"].values())
               for s in done) / len(done)
