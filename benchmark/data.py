"""The one generator of training data: a traffic mix gives the batch and
the steps an epoch, the configuration's ``input`` says what a row is, and
the seed gives the rows."""

from __future__ import annotations

import numpy as np


def _image_patches(rng, n, cfg):
    """uint8 images of ``patch_grid`` x ``patch_grid`` colour patches and
    labels (copied from ``chip_smoke.synthetic_images``).  Patches, not
    per-pixel noise: noise averages to the same features for every image."""
    grid = cfg["input"]["patch_grid"]
    size = cfg["image_size"]
    if size % grid:
        raise ValueError(f"image_size {size} is no multiple of {grid}")
    patches = rng.integers(0, 256, size=(n, grid, grid, 3), dtype=np.uint8)
    side = size // grid
    x = np.repeat(np.repeat(patches, side, axis=1), side, axis=2)
    y = rng.integers(0, cfg["num_classes"], size=(n,)).astype(np.int32)
    return x, y


def _token_ids(rng, n, cfg):
    """Token ids and next-token targets drawn uniformly below the
    vocabulary's size."""
    shape = (n, cfg["n_positions"])
    x = rng.integers(0, cfg["vocab_size"], size=shape, dtype=np.int32)
    y = rng.integers(0, cfg["vocab_size"], size=shape, dtype=np.int32)
    return x, y


_KINDS = {"image_patches": _image_patches, "token_ids": _token_ids}


def rows(seed: int, stream: int, n: int, cfg: dict):
    """``n`` rows of the configuration's input from ``seed``; ``stream``
    tells the window's rows from the check steps'."""
    rng = np.random.default_rng([int(stream), int(seed)])
    return _KINDS[cfg["input"]["kind"]](rng, n, cfg)
