"""Small shared utilities: shape helpers."""

from __future__ import annotations


def to_tuple_shape(shape) -> tuple:
    """Normalize a shape argument to a tuple of ints/None."""
    if shape is None:
        return ()
    if isinstance(shape, int):
        return (shape,)
    return tuple(shape)


def canonicalize_axis(axis: int, ndim: int) -> int:
    if axis < 0:
        axis += ndim
    if not 0 <= axis < ndim:
        raise ValueError(f"axis {axis} out of range for ndim {ndim}")
    return axis
