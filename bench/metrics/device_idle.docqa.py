"""Share of the traced stretch in which no operation ran on the device."""


def read(run):
    s = run.summary
    return None if s is None else 100.0 * s.idle_share
