"""String corpora, one module per dataset, each a copy of its generator
in the program's ``repro/data/strings.py`` (kept as it is there, so that
the benchmark's data do not move when the program's copy changes).

A configuration names its dataset by module: ``"dataset": "usps"`` loads
``bench/datasets/usps.py`` and calls its ``make(n, seed)``.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class StringDataset:
    name: str
    strings: list[str]
    scores: np.ndarray
    rules: list[tuple[str, str]]   # (query-side lhs, dictionary-side rhs)


def _scores(rng, n):
    return rng.integers(1, 50_001, n).astype(np.int32)
