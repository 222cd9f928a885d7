"""The plain reference of a two-party merge (MAGE, OSDI 2021, sec. 8.1.1):
each party holds ``n`` records sorted by key, and the output is all
``2n`` of them sorted by key.

A record is one integer: its key in the low ``key_width`` bits, its
payload above them.  Job ``k`` of a run with seed ``s`` draws from
``default_rng([s, k])``, for each party in the order of the
configuration's ``inputs``, ``n`` keys below ``2**key_bits`` and then
``n`` payloads below ``2**payload_bits``; the party's records are sorted
by key.  Input tag ``tag_base + i`` is the party's ``i``-th chunk of
``chunk`` records; output tag ``output.tag_base + i`` the merge's.

A merging network need not be stable, so records with equal keys may come
out in any order: ``canonical`` sorts each run of equal keys, as they lie
in the output, by whole record, and two outputs are the same where their
canonical forms are equal (``exact``).  So a record out of key order, and
a record altered or lost inside a run, is a mismatch.  Nothing here
imports the program.
"""

from __future__ import annotations

import numpy as np

COMPARE = "exact"


def _key(config: dict, records: np.ndarray) -> np.ndarray:
    return records & np.uint64((1 << int(config["records"]["key_width"]))
                               - 1)


def arrays(config: dict, seed: int, job: int) -> list[np.ndarray]:
    """One uint64 array of ``n`` key-sorted records per party."""
    n = int(config["job"]["n"])
    rec = config["records"]
    rng = np.random.default_rng([seed, job])
    out = []
    for _ in config["inputs"]:
        keys = rng.integers(0, 1 << int(rec["key_bits"]), n, np.uint64)
        payload = rng.integers(0, 1 << int(rec["payload_bits"]), n,
                               np.uint64)
        records = keys | (payload << np.uint64(rec["key_width"]))
        out.append(records[np.argsort(keys, kind="stable")])
    return out


def _chunks(config: dict, base: int, records: np.ndarray) -> dict:
    c = int(config["chunk"])
    return {base + i: records[i * c:(i + 1) * c]
            for i in range(len(records) // c)}


def provider(config: dict, arrays: list[np.ndarray]):
    """tag -> chunk of records, as the program's INPUT instructions ask."""
    table = {}
    for spec, records in zip(config["inputs"], arrays):
        table.update(_chunks(config, int(spec["tag_base"]), records))

    def value(tag: int) -> np.ndarray:
        if tag not in table:
            raise KeyError(f"no input with tag {tag}")
        return table[tag]
    return value


def merged(config: dict, arrays: list[np.ndarray]) -> np.ndarray:
    """Every party's records, in one list sorted by key."""
    both = np.concatenate(arrays)
    return both[np.argsort(_key(config, both), kind="stable")]


def expected(config: dict, arrays: list[np.ndarray]) -> dict:
    return _chunks(config, int(config["output"]["tag_base"]),
                   merged(config, arrays))


def canonical(config: dict, outputs: dict) -> dict:
    """``outputs`` with each run of equal keys, over the chunks in tag
    order, sorted by whole record; keys stay where they are."""
    tags = sorted(outputs)
    parts = [np.asarray(outputs[t]).astype(np.uint64) for t in tags]
    flat = np.concatenate(parts) if parts else np.zeros(0, np.uint64)
    key = _key(config, flat)
    run = np.concatenate([[0], np.cumsum(key[1:] != key[:-1])])
    flat = flat[np.lexsort((flat, run))]
    ends = np.cumsum([len(p) for p in parts])
    return dict(zip(tags, np.split(flat, ends[:-1])))
