"""Tables and queries of the benchmark's configurations, from a seed.

A configuration names its table and its query family; each is a file of
the benchmark's own, found by that name through a
:class:`qdbench.spec.Spec`: ``tables/<name>.py`` gives ``schema()`` and
``make(n, seed)``, ``families/<name>.py`` gives ``queries(schema, n,
seed)``, ``MULTIPLE`` (query counts are multiples of it) and, where the
family has templates, ``templates(schema)``: name -> ``fn(rng) ->
query``.  They are the benchmark's own copies of the repository's
generators, so a later change to the program's generators does not move
the yardstick.

A schema is a tuple of :class:`Col`.  A query is a DNF: a tuple of
conjuncts, each a tuple of atoms, where an atom is one of

    ("range", dim, op, literal)   op in "<", "<=", ">", ">="
    ("in", dim, values)           values a sorted tuple of codes
    ("adv", dim_a, op, dim_b)     column-versus-column comparison

Tables are dictionary-encoded int32 matrices, one column per schema entry.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Col:
    name: str
    kind: str  # "numeric" | "categorical"
    dom: int  # codes live in [0, dom)


class Schema(tuple):
    """A tuple of :class:`Col` with the lookups the reference needs."""

    def dim(self, name: str) -> int:
        for i, c in enumerate(self):
            if c.name == name:
                return i
        raise KeyError(name)

    @property
    def doms(self) -> np.ndarray:
        return np.array([c.dom for c in self], np.int64)

    @property
    def is_cat(self) -> np.ndarray:
        return np.array([c.kind == "categorical" for c in self], bool)

    @property
    def cat_offsets(self) -> np.ndarray:
        """Offset of each categorical column in the concatenated bit space
        (-1 for numeric columns)."""
        off = np.full(len(self), -1, np.int64)
        pos = 0
        for i, c in enumerate(self):
            if c.kind == "categorical":
                off[i] = pos
                pos += c.dom
        return off

    @property
    def cat_bits(self) -> int:
        return int(sum(c.dom for c in self if c.kind == "categorical"))


def in_atom(dim: int, values) -> tuple:
    """An IN atom over ``values``, as a sorted tuple of distinct codes."""
    return ("in", dim, tuple(sorted({int(v) for v in values})))


def make_table(spec, name: str, n: int,
               seed: int) -> tuple[Schema, np.ndarray]:
    """The schema of table ``name`` and ``n`` of its rows from ``seed``."""
    table = spec.load("tables", name)
    return table.schema(), table.make(n, seed)


def make_queries(spec, family: str, schema: Schema, n: int, seed: int) -> list:
    """``n`` build queries of a family, from ``seed``."""
    return spec.load("families", family).queries(schema, n, seed)


def templates(spec, family: str, schema: Schema) -> dict:
    """A family's templates: name -> ``fn(rng) -> query``."""
    fam = spec.load("families", family)
    if not hasattr(fam, "templates"):
        raise ValueError(f"query family {family!r} has no templates "
                         f"({fam.__file__} gives no templates(schema))")
    return fam.templates(schema)
