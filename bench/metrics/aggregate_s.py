"""aggregate_s (s): time under the program's ``aires.aggregate`` spans in
the study: per point, the budget and overflow checks, the pooled
histograms and the host statistics."""


def read(view):
    found = [e - s for n, s, e in view.host if n == "aires.aggregate"]
    return sum(found) * 1e-9 if found else None
