"""host_tail_s (s): from the end of the sweep program on the last chip to
finish it to the end of the study span: the transfer of the outputs, their
slicing and the host aggregation."""


def read(view):
    progs = [c.program() for c in view.chips if c.program()]
    if not progs:
        return None
    return (view.span[1] - max(p[2] for p in progs)) * 1e-9
