"""A fresh instance of the family's templates for every request, equally
many of each template, in an order shuffled by the seed."""

from __future__ import annotations

import numpy as np

from qdbench import datagen
from qdbench.traffic import apportion


def requests(spec, traffic: dict, cfg: dict, schema, n: int,
             seed: int) -> list:
    rng = np.random.default_rng(seed)
    templates = datagen.templates(spec, cfg["query_family"], schema)
    fns = list(templates.values())
    picks = np.repeat(np.arange(len(fns)), apportion(np.ones(len(fns)), n))
    rng.shuffle(picks)
    return [fns[i](rng) for i in picks]
