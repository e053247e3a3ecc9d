"""race_kernel_share (%): device time of the event_race kernel over the
time the sweep program ran, mean over the cell's chips."""

from bench.tracereduce import RACE_KERNEL


def read(view):
    shares = []
    for c in view.chips:
        prog = c.program()
        race = sum(e - s for n, s, e in c.ops if RACE_KERNEL in n)
        if prog and race:
            shares.append(race / (prog[2] - prog[1]))
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
