"""Host milliseconds from entry into ``matvec``/``matmat`` until it
returns, before the wait: the dispatch the host does per product
(layer: executor)."""


def read(run):
    calls = run.ok_calls
    if not calls:
        return None
    return 1e3 * sum(c.returned - c.issued for c in calls) / len(calls)
