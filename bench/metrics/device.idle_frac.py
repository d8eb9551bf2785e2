"""Share of the traced window in which the busiest chip ran no operation:
1 - (union of its device op intervals) / window, in percent."""


def read(obs):
    tr = obs.get("trace")
    return 100.0 * tr["idle_frac"] if tr else None
