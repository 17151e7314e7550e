"""Compile plane: seconds in ``compile_step`` / ``timed_compile`` during
set-up, summed over the labels of the program's ``zoo_compile_seconds``
(a load from the persistent cache is timed there too)."""


def read(run):
    sums = [value for (name, _label), (value, _n)
            in run["registry_before"].items()
            if name == "zoo_compile_seconds"]
    return sum(sums) if sums else None
