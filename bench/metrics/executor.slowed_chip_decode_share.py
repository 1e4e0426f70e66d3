"""Of the window's products made while a chip was slowed, the share
whose decode used a worker of that chip, in % (layer: executor).  Near
0 means the harvest decodes without the slowed chip."""


def read(run):
    per = getattr(run.stream, "per", None)
    slowed = [c.spec for c in run.ok_calls
              if per and getattr(c.spec, "slowed", -1) >= 0
              and getattr(c.spec, "harvest_s", None) is not None]
    if not slowed:
        return None
    used = sum(any(i // per == s.slowed for i in s.rows) for s in slowed)
    return 100.0 * used / len(slowed)
