"""program_s (s): how long the sweep program (the longest XLA module
execution in the study span) ran on the device, from its first start to
its last end over the cell's chips."""


def read(view):
    progs = [c.program() for c in view.chips if c.program()]
    if not progs:
        return None
    return (max(p[2] for p in progs) - min(p[1] for p in progs)) * 1e-9
