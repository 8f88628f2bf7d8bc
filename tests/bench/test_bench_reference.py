"""The benchmark's plain reference agrees with the program's
``OracleIndex`` on small dictionaries, and its tie contract refuses
altered answers."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import loadgen  # noqa: E402
from bench.datasets import dblp, usps  # noqa: E402
from bench.references.prefix_scan import PrefixScan, agrees  # noqa: E402
from repro.core import make_rules  # noqa: E402
from repro.core.oracle import OracleIndex  # noqa: E402

K = 10


@pytest.mark.parametrize("make,generator", [(usps.make, "zipf"),
                                            (dblp.make, "uniform")])
def test_prefix_scan_equals_the_oracle(make, generator):
    ds = make(1200, seed=loadgen.seed_seq(5, 0))
    spec = {"generator": generator, "a": 1.3, "min_len": 1, "max_len": 24}
    queries = loadgen.make_queries(ds, spec, 150, loadgen.seed_seq(5, 2))
    scan = PrefixScan(ds.strings, ds.scores, ds.rules)
    oracle = OracleIndex(ds.strings, ds.scores, make_rules(ds.rules))
    n_rewritten = 0
    for q in queries + [q[:1] for q in queries[:20]]:
        want = [(s, x.decode()) for s, x in oracle.complete(q, K)]
        assert scan.complete(q, K) == want, q
        assert scan.verdict(q, want, K) is None
        n_rewritten += len(scan.rows(q)) > 0 and \
            sum(q.encode().find(l.encode()) >= 0 for l, _ in ds.rules) > 0
    assert n_rewritten > 10       # the rules are exercised


def test_the_tie_contract_refuses_altered_answers():
    ds = usps.make(1500, seed=loadgen.seed_seq(6, 0))
    scan = PrefixScan(ds.strings, ds.scores, ds.rules)
    q = "jim"
    want = scan.complete(q, K)
    assert len(want) == K
    assert scan.verdict(q, want, K) is None
    # a string above the k-th score replaced by a non-match
    bad = [want[0][:1] + ("zz no such address",)] + want[1:]
    assert scan.verdict(q, bad, K) is not None
    # a score changed, a place dropped, the order broken
    assert scan.verdict(q, [(want[0][0] + 1, want[0][1])] + want[1:],
                        K) is not None
    assert scan.verdict(q, want[:-1], K) is not None
    assert scan.verdict(q, want[::-1], K) is not None
    # a tied last place may hold any match with that score
    kth = want[-1][0]
    assert agrees(want, want, lambda x: True) is None
    assert agrees(want, want[:-1] + [(kth, "elsewhere")],
                  scan.tie_ok(q, kth)) is not None
