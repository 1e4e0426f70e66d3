"""Host milliseconds from a placed product's last chip launch until the
chips that finished first hold k workers' results, the mean over the
window's products, as the executor reports it (layer: executor)."""


def read(run):
    waits = [c.spec.harvest_s for c in run.ok_calls
             if getattr(c.spec, "harvest_s", None) is not None]
    return 1e3 * sum(waits) / len(waits) if waits else None
