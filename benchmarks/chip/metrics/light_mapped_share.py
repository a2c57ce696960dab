"""Share of the window's pairs that light alignment mapped (StageStats
``light_mapped / n_pairs``); the pairs lane only."""


def read(run):
    n = run.totals.get("n_pairs", 0)
    if not n:
        return None
    return 100.0 * run.totals["light_mapped"] / n
