"""l0_fp64_block_share: share of the width >= 3 ℓ0 blocks on the
Gram-gather path that the fp32 prescreen could not certify and that were
rescored whole in fp64, in percent, from the program's own counter
``stats["l0_paths"]`` over the traced window's fits (``fit_stats``); None
where the program keeps no such counter or no block of width >= 3 took the
Gram-gather path."""
from benchmarks.suite import fit_stats

KERNEL = "Gram-gather kernel"
FALLBACK = "exact fp64 (window not certified)"


def read(run):
    fallback = total = 0
    for s in fit_stats.window_stats(run) or []:
        for width, paths in s.get("l0_paths", {}).items():
            if int(width) < 3:
                continue
            fallback += paths.get(FALLBACK, 0)
            total += paths.get(FALLBACK, 0) + paths.get(KERNEL, 0)
    return 100.0 * fallback / total if total else None
