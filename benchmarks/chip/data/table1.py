"""The 22 sparsest Table-1 stand-ins, read from the committed patterns.

``generate`` returns the matrices the configuration lists, in its order,
and refuses a pattern whose n or nnz is not the published one.  The
patterns are fixed data: the seed does not change them.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np


def generate(params: dict, seed: int) -> list:
    del seed
    path = Path(__file__).with_name(params["file"])
    with np.load(path) as z:
        out = []
        for spec in params["matrices"]:
            name = spec["name"]
            indptr = z[f"{name}.indptr"]
            indices = z[f"{name}.indices"]
            if len(indptr) != spec["n"] + 1 or indptr[-1] != spec["nnz"]:
                raise ValueError(f"{name}: pattern is n={len(indptr) - 1} "
                                 f"nnz={indptr[-1]}, published n={spec['n']}"
                                 f" nnz={spec['nnz']}")
            out.append((name, indptr, indices, spec["n"]))
    return out
