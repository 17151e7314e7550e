"""Compile plane: seconds in ``jit(...).lower()`` during set-up, the
step's trace and lowering, which no cache holds: summed over the labels of
the program's ``zoo_lower_seconds`` (span ``zoo.compile.lower``)."""


def read(run):
    sums = [value for (name, _label), (value, _n)
            in run["registry_before"].items()
            if name == "zoo_lower_seconds"]
    return sum(sums) if sums else None
