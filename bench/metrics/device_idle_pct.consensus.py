"""Share of the traced consensus window in which no operation ran on the
chip: 100 (1 - busy / window). Layer: api (Session.run's segment loop,
value upload, readback). Moves consensus_rounds_per_s."""


def read(view):
    s = view["summary"]
    return 100.0 * (1.0 - s.busy_s / s.window_s)
