"""DBLP publication titles (paper Table 1) over a CS word vocabulary with
abbreviation rules.

The title generator is the program's ``make_dblp``
(``repro/data/strings.py``); the rules are the benchmark's own.  The
program's generator makes 68 rules where the paper's DBLP set has 368
(its cap of 214 never binds), so this copy makes 368 abbreviations of
the vocabulary's words, of the kinds the program's generator makes and
their like, family by family over the words in sorted order:

1. the first three letters and a period (``dat.`` -> ``database``),
   words of six letters or more (the program's first kind);
2. the first four letters (``data``), words of eight letters or more
   (the program's second kind);
3. the consonant skeleton: the first letter and the later consonants
   without repeats (``dtbs``), where it has three letters or more;
4. longer truncations with a period, four letters and up, stopping two
   letters short of the word (``data.``, ``datab.``);
5. the same truncations without the period, four letters and up
   (``datab``, ``distrib``).

The first ``RULES`` distinct pairs are kept.
"""

from __future__ import annotations

import numpy as np

from bench.datasets import StringDataset, _scores

#: the paper's DBLP rule count (Table 1)
RULES = 368

_WORDS = """system database management query optimization transaction neural
network learning deep graph stream processing distributed parallel storage
index structure algorithm efficient scalable adaptive dynamic incremental
approximate probabilistic semantic knowledge information retrieval search
ranking completion string synonym abbreviation entity resolution join
similarity vector spatial temporal crowd privacy secure federated quantum
""".split()


def _skeleton(w: str) -> str:
    out = w[0]
    for c in w[1:]:
        if c not in "aeiou" and c != out[-1]:
            out += c
    return out


def _rules() -> list[tuple[str, str]]:
    words = sorted(set(_WORDS))
    pairs = [(w[:3] + ".", w) for w in words if len(w) >= 6]
    pairs += [(w[:4], w) for w in words if len(w) >= 8]
    pairs += [(_skeleton(w), w) for w in words
              if len(_skeleton(w)) >= 3 and _skeleton(w) != w]
    longest = max(map(len, words))
    pairs += [(w[:j] + ".", w) for j in range(4, longest)
              for w in words if j <= len(w) - 2]
    pairs += [(w[:j], w) for j in range(4, longest)
              for w in words if j <= len(w) - 2]
    kept = list(dict.fromkeys(pairs))
    assert len(kept) >= RULES, len(kept)
    return kept[:RULES]


def make_dblp(n: int = 24_810, seed: int = 0) -> StringDataset:
    """Titles from a CS word vocabulary; rules = abbreviation -> word."""
    rng = np.random.default_rng(seed)
    strings = set()
    while len(strings) < n:
        k = rng.integers(4, 10)
        strings.add(" ".join(rng.choice(_WORDS, k)))
    strings = sorted(strings)
    return StringDataset("DBLP", strings, _scores(rng, len(strings)),
                         sorted(_rules()))


make = make_dblp
