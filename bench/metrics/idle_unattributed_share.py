"""idle_unattributed_share (%): of the first chip's idle time in the study
span (its gaps, as the breakdown finds them), the share that lies under
none of the program's ``aires.prepare``, ``aires.wait``,
``aires.transfer`` and ``aires.aggregate`` spans."""

from bench.tracereduce import gaps, union_ns

PARTS = ("aires.prepare", "aires.wait", "aires.transfer", "aires.aggregate")


def read(view):
    parts = [(s, e) for n, s, e in view.host if n in PARTS]
    if not parts or not view.chips[0].ops:
        return None
    idle = gaps([(s, e) for _, s, e in view.chips[0].ops], *view.span)
    idle_ns = sum(e - s for s, e in idle)
    if not idle_ns:
        return None
    covered = union_ns([(max(s, gs), min(e, ge)) for gs, ge in idle
                        for s, e in parts if s < ge and e > gs])
    return 100.0 * (idle_ns - covered) / idle_ns
