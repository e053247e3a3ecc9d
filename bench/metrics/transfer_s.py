"""transfer_s (s): time under the program's ``aires.transfer`` spans in
the study: the device-to-host copy of the outputs and their slicing into
points, after the sweep program's outputs are ready."""


def read(view):
    found = [e - s for n, s, e in view.host if n == "aires.transfer"]
    return sum(found) * 1e-9 if found else None
