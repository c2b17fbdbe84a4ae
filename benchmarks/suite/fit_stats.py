"""The program's own counters (``SissoFit.stats``) of a run's window fits.

A ``FitRecord`` keeps a fit's ``timings`` but not its ``stats``.  The
program keeps the records of its last fits
(``repro.runtime.trace.recent_fits``), and nothing fits between the window
and the readers, so the window's fits are the newest records: each is
checked against its ``FitRecord`` by the length of its ``sisso.fit`` span,
which the fit reported as ``timings["fit"]``.
"""


def window_stats(run):
    """Each window fit's ``stats``, in order; None where the program keeps
    no records of its fits (it has no ``repro.runtime.trace``) or where the
    newest records are not the window's fits."""
    try:
        from repro.runtime.trace import recent_fits
    except ImportError:
        return None
    n = len(run.fits)
    recent = recent_fits()[-n:] if n else []
    if not n or len(recent) != n or any(
            rec.seconds("sisso.fit") != f.timings.get("fit")
            for rec, f in zip(recent, run.fits)):
        return None
    return [rec.stats() for rec in recent]
