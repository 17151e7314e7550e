"""The comparison that decides ``correct`` for a training cell: the timed
object's first steps against the plain reference's, number by number."""

from __future__ import annotations

import statistics

import jax
import jax.numpy as jnp
import numpy as np

#: a leaf whose reference gradient is under this share of the median
#: leaf's is nought to rounding (a key's bias under softmax); Adam moves it
#: by round-off alone, so it is left out of the change
NOUGHT_GRADIENT = 1e-3


@jax.jit
def leaf_norms(tree):
    """The L2 norm of every leaf, as one vector in flattening order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


@jax.jit
def change_norms(after, before):
    return leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        after, before))


def leaf_names(tree) -> list[str]:
    return [jax.tree_util.keystr(path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def leaf_gaps(got, ref, keep=None):
    """The gap between two vectors of leaf norms, leaf by leaf, each
    measured against the reference's norm of that leaf or of the median
    leaf, whichever is larger; -1 for a leaf that is left out."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    keep = np.ones(ref.shape, bool) if keep is None else np.asarray(keep)
    if not keep.any():
        raise ValueError("no leaf left to compare")
    median = statistics.median(ref[keep].tolist())
    gaps = np.abs(got - ref) / np.maximum(np.maximum(ref, median), 1e-300)
    gaps = np.where(np.isfinite(gaps), gaps, np.inf)
    return np.where(keep, gaps, -1.0)


def leaf_differences(got_tree, ref_tree, ref_norms):
    """The norm of the difference of two trees, leaf by leaf, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger; and last the same over the whole tree, against the
    reference's whole norm.  A gap of norms is blind to noise of zero mean
    and to a gradient that points elsewhere at the same length: this is
    not."""
    diff = np.asarray(change_norms(got_tree, ref_tree), np.float64)
    ref = np.asarray(ref_norms, np.float64)
    median = statistics.median(ref.tolist())
    by_leaf = diff / np.maximum(np.maximum(ref, median), 1e-300)
    whole = np.sqrt(np.sum(diff ** 2)) \
        / max(np.sqrt(np.sum(ref ** 2)), 1e-300)
    out = np.append(by_leaf, whole)
    return np.where(np.isfinite(out), out, np.inf)


def all_gaps(got: dict, ref: dict) -> dict:
    """Every gap the comparison can read: a step's loss each, a leaf's
    gradient norm each, a leaf's change each (-1: left out, its reference
    gradient is nought to rounding), and the first gradient's difference
    from the reference's, a leaf each and then the whole tree."""
    losses_got = np.asarray(got["losses"], np.float64)
    losses_ref = np.asarray(ref["losses"], np.float64)
    loss_gaps = np.abs(losses_got - losses_ref) / np.abs(losses_ref)
    grad_ref = np.asarray(ref["grad_norms"], np.float64)
    moved = grad_ref >= NOUGHT_GRADIENT * statistics.median(grad_ref.tolist())
    return {"loss": np.where(np.isfinite(loss_gaps), loss_gaps, np.inf),
            "grad_diff": leaf_differences(got["grads"], ref["grads"],
                                          grad_ref),
            "grad": leaf_gaps(got["grad_norms"], grad_ref),
            "delta": leaf_gaps(got["delta_norms"], ref["delta_norms"],
                               keep=moved)}


def _median(gaps) -> float:
    return float(np.median(gaps[gaps >= 0]))


def compare(got: dict, ref: dict, names: list[str]) -> dict:
    """``got`` and ``ref``: ``losses`` (a step each), ``grad_norms`` and
    ``delta_norms`` (a leaf each) and ``grads`` (the first gradient's
    tree).  Returns every number a cell's limits may hold, with the leaf
    or step it was read at: the widest loss gap; of the first gradient
    and of the change the widest leaf's gap of norms and the median
    leaf's (the widest swings with the noise of one small leaf; the median
    is steady from seed to seed); and the first gradient's difference from
    the reference's by the widest leaf, the median leaf and the whole
    tree."""
    return summarise(all_gaps(got, ref), names)


def summarise(gaps: dict, names: list[str]) -> dict:
    """The numbers of ``compare`` from the gaps of ``all_gaps``."""
    grad_at, delta_at = int(gaps["grad"].argmax()), int(gaps["delta"].argmax())
    diff_by_leaf = gaps["grad_diff"][:-1]
    diff_at = int(diff_by_leaf.argmax())
    return {
        "grad_diff": {"value": float(diff_by_leaf[diff_at]),
                      "at": names[diff_at]},
        "grad_diff_median": {"value": float(np.median(diff_by_leaf)),
                             "at": "the median leaf"},
        "grad_diff_whole": {"value": float(gaps["grad_diff"][-1]),
                            "at": "the whole tree"},
        "loss_gap": {"value": float(gaps["loss"].max()),
                     "at": f"step {int(gaps['loss'].argmax()) + 1}"},
        "grad_gap": {"value": float(gaps["grad"][grad_at]),
                     "at": names[grad_at]},
        "grad_gap_median": {"value": _median(gaps["grad"]),
                            "at": "the median leaf"},
        "delta_gap": {"value": float(gaps["delta"][delta_at]),
                      "at": names[delta_at]},
        "delta_gap_median": {"value": _median(gaps["delta"]),
                             "at": "the median leaf"},
    }


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each number that the cell's limits hold beside its limit; correct
    when none is over."""
    table = {k: {"value": numbers[k]["value"], "limit": limit,
                 "at": numbers[k]["at"]} for k, limit in limits.items()}
    ok = all(np.isfinite(row["value"]) and row["value"] <= row["limit"]
             for row in table.values())
    return bool(ok), table
