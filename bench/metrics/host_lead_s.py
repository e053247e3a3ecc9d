"""host_lead_s (s): from the start of the study span to the start of the
sweep program on the first chip to start it.  The sweep program is the
longest XLA module execution in the span, found by its length and not by
its name."""


def read(view):
    progs = [c.program() for c in view.chips if c.program()]
    if not progs:
        return None
    return (min(p[1] for p in progs) - view.span[0]) * 1e-9
