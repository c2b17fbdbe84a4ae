"""collective_rows: winner rows that the k-sized all-gather merges of a
distributed fit carried across chips, summed over its phases
(``stats["sharded"]["gathered_rows"]``, the program's own counter, counted
on the host from static shapes) and averaged over the traced window's
fits (``fit_stats``); None where the program keeps no such counter."""
from benchmarks.suite import fit_stats


def read(run):
    rows = [s.get("sharded", {}).get("gathered_rows")
            for s in fit_stats.window_stats(run) or []]
    if not rows or None in rows:
        return None
    return sum(sum(r.values()) for r in rows) / len(rows)
