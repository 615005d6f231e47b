"""Share of the traced training window in which no operation ran on the
chip: 100 (1 - busy / window), busy the union of device-op intervals.
Layer: api (the Session host loop between segments). Moves
train_tokens_per_s."""


def read(view):
    s = view["summary"]
    return 100.0 * (1.0 - s.busy_s / s.window_s)
