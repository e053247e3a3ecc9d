"""prepare_s (s): time under the program's ``aires.prepare`` spans in the
study: validation, grouping, the parameter vectors, the initial states
and their bucket padding."""


def read(view):
    found = [e - s for n, s, e in view.host if n == "aires.prepare"]
    return sum(found) * 1e-9 if found else None
