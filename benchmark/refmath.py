"""Small pieces the plain references share. Plain numpy and jax.numpy;
nothing of h2o3_tpu."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

MAX_BLOCK = 65536


def block_rows(n: int) -> int:
    """Rows per block of a blocked reduction: 65,536, or for a small test
    size the next power of two that holds all of them."""
    return MAX_BLOCK if n >= MAX_BLOCK else 1 << max(n - 1, 1).bit_length()


def rounded(x, dtype):
    """x at the precision of `dtype`, still float32. `reduce_precision` is an
    operation of its own: a cast there and back is one the compiler may drop
    (XLA allows excess precision by default)."""
    if dtype is None:
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def auc_exact(score: np.ndarray, y: np.ndarray) -> float:
    """Rank AUC with tied scores sharing their mean rank."""
    order = np.argsort(score, kind="stable")
    s, yy = score[order], y[order]
    n = len(s)
    cut = np.flatnonzero(np.diff(s)) + 1
    starts = np.concatenate([[0], cut])
    ends = np.concatenate([cut, [n]])
    ranks = np.repeat((starts + 1 + ends) / 2.0, ends - starts)
    npos = float(yy.sum())
    return float((ranks[yy > 0].sum() - npos * (npos + 1) / 2)
                 / (npos * (n - npos)))
