"""Device programs: the share of the rows the window's solo `match` programs
gathered that were padding, in per cent: 1 - real rows over padded rows
(`es.search.solo.rows`: the sparse posting-block rows plus the dense rows a
plan gathers for its terms; `es.search.solo.padded_rows`: the same with the
padding to its program's tiers in the family of PR 38). 0 where no solo search
ran in the window; nothing where the server ships no such counter (a program
from before the family)."""

from benchlib import waves

ROWS = "es.search.solo.rows"
PADDED = "es.search.solo.padded_rows"


def read(run):
    real = waves.mean(run, [ROWS], PADDED)
    if real is None:
        return None
    return 100.0 * (1.0 - real) if waves.added(run, PADDED) else 0.0
