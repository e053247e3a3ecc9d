"""device_idle_share (%): the share of the study span in which no
operation ran on a chip, mean over the cell's chips."""


def read(view):
    if not any(c.ops for c in view.chips):
        return None
    span = view.span[1] - view.span[0]
    idle = [1.0 - c.busy_ns() / span for c in view.chips]
    return 100.0 * sum(idle) / len(idle)
