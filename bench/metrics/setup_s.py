"""Seconds from process start to the first due request: import, weights,
join plans, warm-up of every shape the cell's traffic can use."""
NAME, UNIT, BETTER, SOURCE = "setup_s", "s", "lower", "host_clock"


def compute(rec):
    return rec.setup_s
