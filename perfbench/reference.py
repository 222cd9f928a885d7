"""The plain reference: what each job's outputs should be, in numpy.

A configuration states its result as one einsum over its inputs, each
value a vector of slots (the letter ``s``).  The contracted indices are
summed one term at a time in index order, every partial sum rounded to
``dtype``: float64 is the reference, float16 the control that has to fail.
Output ``t`` of the job is element ``t - tag_base`` of the flattened
result.  Nothing here imports the program.
"""

from __future__ import annotations

import numpy as np


def _terms(einsum: str) -> tuple[str, str, str]:
    """(inputs, output, contracted letters) of ``"ijs,jks->iks"``."""
    ins, out = einsum.replace(" ", "").split("->")
    letters = "".join(dict.fromkeys(c for c in ins if c not in ",>"))
    summed = "".join(c for c in letters if c not in out)
    return ins, out, summed


def result(output: dict, arrays: list[np.ndarray],
           dtype=np.float64) -> np.ndarray:
    """The einsum of ``output`` over ``arrays``, shaped like its output
    subscripts, accumulated term by term in ``dtype``."""
    ins, out, summed = _terms(output["einsum"])
    xs = [np.asarray(a).astype(dtype) for a in arrays]
    if not summed:
        return np.einsum(f"{ins}->{out}", *xs).astype(dtype)
    # every product term kept apart, contracted letters first
    terms = np.einsum(f"{ins}->{summed}{out}", *xs)
    terms = terms.reshape((-1,) + terms.shape[len(summed):]).astype(dtype)
    acc = terms[0].copy()
    for t in terms[1:]:
        acc = (acc + t).astype(dtype)
    return acc


def outputs(output: dict, arrays: list[np.ndarray],
            dtype=np.float64) -> dict[int, np.ndarray]:
    """tag -> expected slot vector."""
    r = result(output, arrays, dtype)
    rows = r.reshape(-1, r.shape[-1])
    base = int(output["tag_base"])
    return {base + i: rows[i] for i in range(len(rows))}
