"""The plain reference of a configuration that states its result as one
einsum over its inputs; the reference of every configuration that names
no other.

Inputs: the configuration names them by tag base and shape (``"n"`` in a
shape is the job's problem size); every value is a vector of ``slots``
reals drawn uniformly from [-1, 1).  Job ``k`` of a run with seed ``s``
draws from ``default_rng([s, k])``.

Result: the einsum's contracted indices are summed one term at a time in
index order, every partial sum rounded to ``dtype``: float64 is the
reference, float16 the control that has to fail.  Output ``t`` of the job
is element ``t - tag_base`` of the flattened result, compared by the
widest gap of a slot (``max_err``).  Nothing here imports the program.
"""

from __future__ import annotations

import numpy as np

COMPARE = "max_err"


def shape_of(spec: list, n: int) -> tuple[int, ...]:
    return tuple(n if d == "n" else int(d) for d in spec)


def arrays(config: dict, seed: int, job: int) -> list[np.ndarray]:
    """One array per input, shaped (*shape, slots), float64."""
    n, slots = int(config["job"]["n"]), int(config["slots"])
    rng = np.random.default_rng([seed, job])
    return [rng.uniform(-1.0, 1.0, shape_of(s["shape"], n) + (slots,))
            for s in config["inputs"]]


def provider(config: dict, arrays: list[np.ndarray]):
    """tag -> slot vector, as the program's INPUT instructions ask."""
    slots = int(config["slots"])
    flat = [(int(s["tag_base"]), a.reshape(-1, slots))
            for s, a in zip(config["inputs"], arrays)]

    def value(tag: int) -> np.ndarray:
        for base, rows in flat:
            if base <= tag < base + len(rows):
                return rows[tag - base]
        raise KeyError(f"no input with tag {tag}")
    return value


def expected(config: dict, arrays: list[np.ndarray]) -> dict:
    return outputs(config["output"], arrays)


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
