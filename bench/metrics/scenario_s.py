"""scenario_s (s): time under the program's ``aires.scenario`` spans in
the study: the host-side set-up of fault domains and campaigns (their
parameter columns, step budget and kill fractions), inside
``aires.prepare``."""


def read(view):
    found = [e - s for n, s, e in view.host if n == "aires.scenario"]
    return sum(found) * 1e-9 if found else None
