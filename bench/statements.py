"""Structured SQL statements: what the traffic generators make, what the
reference evaluates, and the text the program receives.

A predicate tree is ``("cmp", col, op, value)``, ``("and", children)`` or
``("or", children)``. The program only ever sees ``Stmt.sql``; the reference
evaluates the tree itself, so no parser is shared between them.
"""
from __future__ import annotations

import dataclasses

import numpy as np

OPS = ("<", "<=", ">", ">=", "=", "!=")


@dataclasses.dataclass(frozen=True)
class Stmt:
    func: str                 # COUNT, SUM, AVG, MIN, MAX, MEDIAN, VAR
    agg: str                  # column name or "*"
    where: tuple | None       # predicate tree
    group_by: str | None = None

    def sql(self, table: str) -> str:
        text = f"SELECT {self.func}({self.agg}) FROM {table}"
        if self.where is not None:
            text += " WHERE " + render(self.where, top=True)
        if self.group_by is not None:
            text += f" GROUP BY {self.group_by}"
        return text


def literal(value) -> str:
    if isinstance(value, str):
        return f"'{value}'"
    return repr(float(value))


def render(tree, top: bool = False) -> str:
    kind = tree[0]
    if kind == "cmp":
        _, col, op, value = tree
        return f"{col} {op} {literal(value)}"
    glue = " AND " if kind == "and" else " OR "
    text = glue.join(render(ch) for ch in tree[1])
    return text if top else f"({text})"


def conj(*conds) -> tuple:
    """AND of ``(col, op, value)`` conditions (a single one stays bare)."""
    leaves = tuple(("cmp", c, o, v) for c, o, v in conds)
    return leaves[0] if len(leaves) == 1 else ("and", leaves)


def columns_of(tree) -> set:
    if tree is None:
        return set()
    if tree[0] == "cmp":
        return {tree[1]}
    out = set()
    for ch in tree[1]:
        out |= columns_of(ch)
    return out


def decimals(values: np.ndarray, most: int = 6) -> int:
    """Fewest decimals that represent every sampled value exactly (the
    quantization of the column), as the program's dataset suite infers it."""
    finite = values[np.isfinite(values)][:10000]
    for p in range(most + 1):
        if np.all(np.abs(finite * 10**p - np.round(finite * 10**p)) < 1e-6):
            return p
    return most
