"""Stand-ins for the program that must come out not correct.

- :class:`Control`: the plain reference put in the program's place, with
  its operands rounded to bfloat16, the precision below the float32 the
  configurations state.  It sets the upper reading of ``max_rel_err``.
- :class:`Stale`, :class:`DropHalf`, :class:`Altered`: the program with
  its timed path broken underneath, one fault each: a call that returns
  the previous call's result, a result with half of its entries left out,
  and a result with one entry altered where it is produced.

The benchmark's own runs never use them.  ``control.py`` runs them on the
chip at a cell's size; the tests run them at a tiny size on the CPU.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import reference
from traffic import Program


@dataclasses.dataclass
class _Result:
    values: np.ndarray
    row_indices: np.ndarray
    col_ptr: np.ndarray


class Control(Program):
    """C from the reference over bfloat16-rounded operands: a plan is the
    matrix it was made from, and nothing of the program runs."""

    def __init__(self, backend: str, method: str):
        super().__init__(backend, method)
        self._matrix = {}

    def operand(self, m, values):
        self._matrix[id(values)] = m
        return values

    def plan(self, a):
        return self._matrix[id(a)]

    def build_stream(self, plan):
        return None

    def execute(self, plan, values):
        v = reference.bf16_values(values)
        indptr, indices, data = reference.product(plan.pattern, plan.pattern,
                                                  v, v)
        return _Result(data.astype(np.float32), indices, indptr)

    def plan_cache_info(self) -> dict:
        return {"max_size": 0, "hits": 0}

    def release(self) -> None:
        pass


class Stale(Program):
    """Every call returns the previous call's result: state one call old."""

    def __init__(self, backend: str, method: str):
        super().__init__(backend, method)
        self._prev = None

    def execute(self, plan, values):
        c = super().execute(plan, values)
        out, self._prev = (c if self._prev is None else self._prev), c
        return out


class DropHalf(Program):
    """The second half of C's entries left out (zero)."""

    def execute(self, plan, values):
        c = super().execute(plan, values)
        keep = np.arange(c.values.shape[0]) < c.values.shape[0] // 2
        return dataclasses.replace(c, values=np.where(keep, c.values, 0))


class Altered(Program):
    """One entry of C off by one percent."""

    def execute(self, plan, values):
        c = super().execute(plan, values)
        v = np.array(c.values)
        v[len(v) // 3] *= 1.01
        return dataclasses.replace(c, values=v)


FAULTS = {"stale": Stale, "drop_half": DropHalf, "altered": Altered}
