"""Smoke run of the completion path on a TPU.

    python chip_smoke.py                # one chip: USPS at 1,000,000 strings
    python chip_smoke.py --four-chips   # only the sharded-index phase, 4 chips

One process drives the normal entry points and catches nothing.  It
builds the paper's USPS deployment (``configs/autocomplete.py``) through
``build_index`` with the serving launcher's default spec, prints what was
built and, for each phase, the substrate dispatches its programs traced
(``repro.core.engine.primitives.record_dispatch``), and checks three
phases:

  (a) ``CompletionService.complete`` on a few query batches and on 1-3
      character prefixes; every answer must agree with a plain scan of
      the sorted strings, and a sample with ``OracleIndex`` on the same
      data (the same top-k scores and strings, up to which of several
      strings tied at the k-th score is returned; see ``agrees``);
  (b) a ``Session`` typing queries char by char must equal the one-shot
      answer at every prefix;
  (c) ``CompletionService(batching=True)`` with 16 sessions must be
      bit-identical to sequential replay.

Last, an explicit ``substrate="pallas"`` must raise the TPU compiler's
own error rather than fall back to jnp.

``--four-chips`` runs only the mesh phase: a ``ShardedCompletionIndex``
over a 4-device mesh must answer exactly like the single-device local
mode over the same shards, with each shard's tables on its own device.

Times printed are smoke times, compilation included, not benchmark
numbers.  Off a TPU, on any failure, and on any mismatch the script
exits non-zero without printing a result.  The last line of standard
output is the contract:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import AxisType  # noqa: E402

from repro.api import IndexSpec, build_index  # noqa: E402
from repro.api.compile_cache import enable_persistent_cache  # noqa: E402
from repro.configs.autocomplete import USPS  # noqa: E402
from repro.core import engine as eng  # noqa: E402
from repro.core import make_rules  # noqa: E402
from repro.core.distributed import ShardedCompletionIndex  # noqa: E402
from repro.core.engine.primitives import record_dispatch  # noqa: E402
from repro.core.oracle import OracleIndex  # noqa: E402
from repro.data.strings import (make_keystroke_events, make_usps,  # noqa: E402
                                make_workload)
from repro.launch.serve import (_replay_batched,  # noqa: E402
                                _replay_sequential)
from repro.serving import CompletionService  # noqa: E402

K = 10
# the serving launcher's default spec (repro.launch.serve)
SPEC = IndexSpec(kind="et", substrate="auto")
BATCHES, BATCH = 4, 64
# the oracle is a dict-of-dicts trie in pure Python: it is built over
# just the strings that can match the sampled queries, capped so that it
# stays well under a minute at a million strings
ORACLE_QUERIES, ORACLE_MAX_STRINGS = 64, 200_000
SESSION_QUERIES = 4
SESSIONS, SESSION_EVENTS_QUERIES = 16, 48


class SmokeFailure(RuntimeError):
    """A phase produced a wrong answer."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(line: str) -> None:
    print(line, flush=True)


def rewritings(q: bytes, rules) -> set[bytes]:
    """Every rewriting of ``q`` under the rules (non-overlapping lhs
    occurrences of the typed query replaced by their rhs): a string can
    match ``q`` only if one of these is a prefix of it."""
    reach: list[set[bytes]] = [set() for _ in range(len(q) + 1)]
    reach[0].add(b"")
    for pos in range(len(q)):
        for pre in reach[pos]:
            reach[pos + 1].add(pre + q[pos:pos + 1])
            for r in rules:
                if q.startswith(r.lhs, pos):
                    reach[pos + len(r.lhs)].add(pre + r.rhs)
    return reach[len(q)]


class PrefixScan:
    """The same semantics as ``OracleIndex`` by another route, cheap
    enough for the broad prefixes the oracle cannot take at a million
    strings: the distinct strings sorted (each at its highest score), the
    matches of ``q`` the union of the sorted ranges its rewritings are
    prefixes of, ranked by score and then string."""

    def __init__(self, strings, scores, rules):
        pairs = sorted(zip((s.encode() for s in strings),
                           np.asarray(scores).tolist()))
        # a duplicate string keeps its highest score, the last of its run
        last = [i for i in range(len(pairs))
                if i + 1 == len(pairs) or pairs[i + 1][0] != pairs[i][0]]
        self.keys = [pairs[i][0] for i in last]
        self.scores = np.array([pairs[i][1] for i in last], np.int64)
        self.rules = rules

    def rows(self, q: str) -> np.ndarray:
        """Indices into ``keys`` of every match of ``q``, ascending."""
        ranges = [(bisect.bisect_left(self.keys, p),
                   # UTF-8 never holds 0xff, so p + 0xff bounds p's range
                   bisect.bisect_left(self.keys, p + b"\xff"))
                  for p in rewritings(q.encode(), self.rules)]
        return np.unique(np.concatenate(
            [np.arange(lo, hi) for lo, hi in ranges] or [np.zeros(0, int)]))

    def complete(self, q: str, k: int) -> list[tuple[int, str]]:
        rows = self.rows(q)
        top = rows[np.lexsort((rows, -self.scores[rows]))[:k]]
        return [(int(self.scores[i]), self.keys[i].decode()) for i in top]

    def tie_ok(self, q: str, kth: int):
        """Whether a string may fill a place tied at score ``kth``: it
        must be a match of ``q`` carrying that score."""
        rows = self.rows(q)

        def ok(x: str) -> bool:
            i = bisect.bisect_left(self.keys, x.encode())
            j = np.searchsorted(rows, i)
            return (j < len(rows) and rows[j] == i
                    and self.keys[i] == x.encode() and self.scores[i] == kth)
        return ok


def oracle_for(scan: PrefixScan, queries):
    """``OracleIndex`` over the strings that can match ``queries``.

    A string enters when a rewriting of a query is its prefix, so every
    string the full-dictionary oracle could return for these queries is
    present and the answers are the same.  Queries are taken in order
    while the candidate set stays under ``ORACLE_MAX_STRINGS``; the
    broad ones it skips are left to the scan.  Returns the oracle, the
    queries it covers, the candidate count and how many queries were
    skipped as too broad."""
    chosen, rows, skipped = [], set(), 0
    for q in queries:
        mine = scan.rows(q)
        if len(mine) > ORACLE_MAX_STRINGS or \
                len(rows.union(mine.tolist())) > ORACLE_MAX_STRINGS:
            skipped += 1
            continue
        rows.update(mine.tolist())
        chosen.append(q)
        if len(chosen) == ORACLE_QUERIES:
            break
    rows = sorted(rows)
    oracle = OracleIndex([scan.keys[i] for i in rows],
                         [int(scan.scores[i]) for i in rows], scan.rules)
    return oracle, chosen, len(rows), skipped


def agrees(want, got, tie_ok) -> str | None:
    """``None`` when ``got`` meets the engine's contract with the
    reference answer ``want``, else what differs.

    The contract is the one the repository's tests hold every index kind
    to (``tests/test_core_oracle.py``): the same top-k score list, and
    every suggestion a match carrying that score.  Among strings tied at
    the k-th score the engine may return any (its beam breaks ties in
    discovery order, the references by string; ROADMAP debt 3.10), so
    above the k-th score the strings must be the reference's exactly,
    and at it they must be distinct and pass ``tie_ok``."""
    if [s for s, _ in got] != [s for s, _ in want]:
        return f"scores {[s for s, _ in got]} != {[s for s, _ in want]}"
    kth = want[-1][0] if want else None
    if sorted(r for r in got if r[0] != kth) != \
            sorted(r for r in want if r[0] != kth):
        return f"strings above the k-th score: {got} != {want}"
    tied = [x for s, x in got if s == kth]
    if len(set(tied)) != len(tied) or not all(map(tie_ok, tied)):
        return f"strings tied at the k-th score {kth}: {tied}"
    return None


def broad_prefixes(queries, n: int) -> list[str]:
    """Up to ``n`` distinct 1-3 character prefixes of ``queries``: the
    widest subtrees, where ties at the k-th score are densest."""
    out = []
    for q in queries:
        for ln in (1, 2, 3):
            p = q[:ln].rstrip()
            if p and p not in out:
                out.append(p)
    return out[:n]


def build_usps(n_strings: int, seed: int = 0):
    t0 = time.perf_counter()
    ds = make_usps(n=n_strings, seed=seed)
    rules = make_rules(ds.rules)
    idx = build_index(ds.strings, ds.scores, rules, SPEC)
    build_s = time.perf_counter() - t0
    return ds, rules, idx, build_s


def phase_batches(ds, rules, idx) -> None:
    """(a) one-shot batches through the service: every answer against the
    prefix scan, a sample against ``OracleIndex``, and the scan against
    the oracle on that sample."""
    queries = make_workload(ds, BATCHES * BATCH, seed=1)
    queries += broad_prefixes(queries, BATCH)
    svc = CompletionService(idx)
    t0 = time.perf_counter()
    got = []
    with record_dispatch() as paths:
        for i in range(0, len(queries), BATCH):
            got.extend(svc.complete(queries[i:i + BATCH], k=K))
    dt = time.perf_counter() - t0
    t1 = time.perf_counter()
    scan = PrefixScan(ds.strings, ds.scores, rules)
    for q, g in zip(queries, got):
        want = scan.complete(q, K)
        wrong = agrees(want, g, scan.tie_ok(q, want[-1][0] if want else 0))
        check(wrong is None, f"phase a: {q!r} disagrees with the prefix "
                             f"scan: {wrong}")
    t2 = time.perf_counter()
    oracle, sample, n_cand, skipped = oracle_for(scan, queries)
    check(len(sample) > 0, "phase a: no query fit the oracle sample")
    score = dict(oracle.items)
    answer = dict(zip(queries, got))
    for q in sample:
        want = [(s, x.decode()) for s, x in oracle.complete(q, K)]
        check(scan.complete(q, K) == want,
              f"phase a: the prefix scan disagrees with the oracle on {q!r}")
        kth = want[-1][0] if want else 0
        matches = oracle.matches(q)
        wrong = agrees(want, answer[q], lambda x: x.encode() in matches
                       and score[x.encode()] == kth)
        check(wrong is None, f"phase a: {q!r} disagrees with the oracle: "
                             f"{wrong}")
    hits = sum(bool(r) for r in got)
    say(f"phase a (CompletionService.complete): {len(queries)} queries "
        f"(shortest {min(map(len, queries))} chars) in "
        f"{-(-len(queries) // BATCH)} batches of {BATCH}, {hits} with "
        f"answers; traced {' '.join(sorted(paths))}; all agree "
        f"with the prefix scan ({t2 - t1:.3f} s); oracle sample "
        f"{len(sample)} queries (shortest {min(map(len, sample))} chars) "
        f"over {n_cand} candidate strings ({skipped} skipped as too broad, "
        f"{len(queries) - len(sample)} left to the scan alone), all agree "
        f"and the scan equals the "
        f"oracle on every one ({time.perf_counter() - t2:.3f} s); smoke "
        f"seconds {dt:.3f}")


def phase_session(ds, idx) -> None:
    """(b) char-by-char session typing vs the one-shot answer."""
    queries = [q for q in make_workload(ds, 64, seed=2) if len(q) >= 12]
    queries = queries[:SESSION_QUERIES]
    check(len(queries) == SESSION_QUERIES, "phase b: too few long queries")
    sess = idx.session(k=K)
    n = 0
    t0 = time.perf_counter()
    with record_dispatch() as paths:
        for q in queries:
            sess.reset()
            for i, ch in enumerate(q):
                got = sess.type(ch)
                want = idx.complete([q[:i + 1]], k=K)[0]
                check(got == want,
                      f"phase b: prefix {q[:i + 1]!r} typed gives {got}, "
                      f"one-shot gives {want}")
                n += 1
    say(f"phase b (Session): {len(queries)} queries, {n} keystrokes, "
        f"every prefix equal to the one-shot answer; traced "
        f"{' '.join(sorted(paths))}; smoke seconds "
        f"{time.perf_counter() - t0:.3f}")


def phase_scheduler(ds, idx) -> None:
    """(c) continuous batching vs sequential replay of the same events."""
    events = make_keystroke_events(ds, SESSIONS, SESSION_EVENTS_QUERIES,
                                   seed=3)
    t0 = time.perf_counter()
    seq = _replay_sequential(CompletionService(idx), events, SESSIONS, K)
    t1 = time.perf_counter()
    svc = CompletionService(idx, batching=True, block=SESSIONS)
    with record_dispatch() as paths:
        bat = _replay_batched(svc, events, SESSIONS, K)
    t2 = time.perf_counter()
    check(len(seq) == len(bat) and seq == bat,
          "phase c: batched replay differs from sequential replay")
    st = svc.scheduler.stats
    say(f"phase c (CompletionService batching=True): {SESSIONS} sessions, "
        f"{len(seq)} keystrokes bit-identical to sequential replay; "
        f"{st.n_flushes} flushes, mean occupancy {st.mean_occupancy:.2f}, "
        f"{st.fallbacks} one-shot fallbacks; batched replay traced "
        f"{' '.join(sorted(paths))}; smoke seconds "
        f"sequential {t1 - t0:.3f} batched {t2 - t1:.3f}")


def phase_pallas_refused(idx) -> None:
    """An explicit ``substrate="pallas"`` must stop in the TPU compiler
    with the compiler's own error, not fall back to jnp (the kernels'
    table gathers do not lower yet; ``tests/test_tpu_compile.py``)."""
    idx.reconfigure(substrate="pallas")
    try:
        with record_dispatch() as paths:
            idx.complete(["james"], k=K)
    except NotImplementedError as e:
        refusal = str(e).strip().splitlines()[0]
    else:
        raise SmokeFailure("substrate='pallas' answered: a kernel compiled "
                           "for the chip, so 'auto' may pick it now")
    finally:
        idx.reconfigure(substrate=SPEC.substrate)
    say(f"pallas refused (explicit substrate='pallas', traced "
        f"{' '.join(sorted(paths))}): {refusal}")


def phase_four_chips(devices, n_strings: int) -> None:
    """A 4-device ``ShardedCompletionIndex`` against local mode over the
    same shards, with every shard's tables on its own device."""
    t0 = time.perf_counter()
    ds = make_usps(n=n_strings, seed=0)
    rules = make_rules(ds.rules)
    mesh = jax.make_mesh((1, 4), ("data", "model"), devices=devices[:4],
                         axis_types=(AxisType.Auto,) * 2)
    sharded = ShardedCompletionIndex(ds.strings, ds.scores, rules,
                                     mesh=mesh, spec=SPEC)
    local = ShardedCompletionIndex.from_shards(sharded.shards, spec=SPEC)
    say(f"four chips: USPS {n_strings} strings in 4 shards "
        f"({[len(s.strings) for s in sharded.shards]}), host build seconds "
        f"{time.perf_counter() - t0:.3f}, substrate {sharded.cfg.substrate}")
    for name in eng.DeviceTrie._fields:
        leaf = getattr(sharded.device_tries, name)
        if leaf is None:
            continue
        homes = [s.device for s in leaf.addressable_shards]
        check(len(set(homes)) == 4
              and all(s.data.shape[0] == 1 for s in leaf.addressable_shards),
              f"four chips: {name} is not one shard per device ({homes})")
        check(len(getattr(local.device_tries, name).devices()) == 1,
              f"four chips: local mode spread {name} over several devices")
    queries = make_workload(ds, BATCHES * BATCH, seed=1)
    t1 = time.perf_counter()
    for i in range(0, len(queries), BATCH):
        batch = queries[i:i + BATCH]
        a, b = sharded.complete(batch, k=K), local.complete(batch, k=K)
        check(a == b, f"four chips: mesh and local answers differ in batch "
                      f"{i // BATCH}")
    homes = sorted(str(s.device) for s in
                   sharded.device_tries.first_child.addressable_shards)
    say(f"four chips: {len(queries)} queries, mesh answers equal local-mode "
        f"answers; shards on {len(set(homes))} distinct devices {homes}; "
        f"smoke seconds {time.perf_counter() - t1:.3f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-index phase on 4 devices")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX sees {devices[0].platform!r} "
                 f"devices); this script does not fall back to the CPU")
    cache = enable_persistent_cache()
    n_strings = USPS.make_config().n_strings
    say(f"chip_smoke: {len(devices)} x {devices[0].device_kind}, compile "
        f"cache {cache}; times below are smoke times, not benchmarks")

    if args.four_chips:
        check(len(devices) >= 4,
              f"--four-chips needs 4 devices, JAX sees {len(devices)}")
        phase_four_chips(devices, n_strings)
    else:
        ds, rules, idx, build_s = build_usps(n_strings)
        stats = devices[0].memory_stats() or {}
        say(f"build: USPS {idx.stats.n_strings} strings, {len(rules)} rules "
            f"(the config declares {USPS.make_config().n_rules}), "
            f"kind {idx.kind}, {idx.stats.bytes_per_string:.1f} B/string; "
            f"host build seconds {build_s:.3f}; device bytes in use "
            f"{stats.get('bytes_in_use', 'not reported')}")
        say(f"substrate: spec {SPEC.substrate!r} resolved to "
            f"{idx.substrate!r}; each phase lists the dispatches its "
            f"programs traced")
        phase_batches(ds, rules, idx)
        phase_session(ds, idx)
        phase_scheduler(ds, idx)
        phase_pallas_refused(idx)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
