"""Mean time between tokens: every gap whose later token falls in the
window, summed, over their count, so stalls (exclusive prefills,
reloads) count in proportion."""


def value(run):
    gaps = run.gaps()
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
