"""The one traffic generator: it reads a mix's parameters from
``bench/traffic/<mix>.json`` and makes the mix's work from the seed.

Every seed gets the same work: the mix is a queue of *rounds*, each
holding the mix's exact proportions.  A ``calls`` round is shuffled by
the seed; a ``requests`` queue takes its sizes in one fixed order for
every seed, and the seed draws only the payloads (prompt tokens), since
a window of a few dozen requests ends inside a round, and which sizes
it then holds would otherwise change with the seed.

Two kinds of mix:

* ``calls``: reductions over resident arrays.  One round is every
  (op, array) pair once.
* ``requests``: an offline queue of generation requests.  One round is
  ``round`` requests whose prompt lengths follow ``prompt_weights``
  over ``prompt_buckets`` exactly, and whose ``max_new`` sit at the
  round's evenly spaced quantiles of the ``max_new`` distribution,
  paired with the lengths and ordered one fixed way.

Adapted from ``repro.data.pipeline.synthetic_requests`` (seeded and
counter based, bucketed lengths), with the distributions of users'
traffic in place of uniform lengths.
"""

from __future__ import annotations

import json
import os
import statistics

import numpy as np

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "traffic")


def load_mix(name: str) -> dict:
    path = os.path.join(TRAFFIC_DIR, f"{name}.json")
    with open(path) as f:
        mix = json.load(f)
    if mix.get("kind") not in ("calls", "requests"):
        raise ValueError(f"{path}: kind must be 'calls' or 'requests'")
    return mix


def _rng(seed: int, *stream) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32, *stream]))


# ------------------------------------------------------------------ calls


def arrays(mix: dict) -> list:
    """``[(array_id, n)]``: the resident arrays of a ``calls`` mix."""
    out = []
    for lg in mix["sizes_log2"]:
        for _ in range(int(mix["arrays_per_size"])):
            out.append((len(out), 1 << int(lg)))
    return out


def calls(mix: dict, seed: int, r: int) -> list:
    """Round ``r`` of the mix: every ``(op, array_id)`` pair once, in
    the seed's order for that round (or in order, with
    ``"shuffle": false``)."""
    pairs = [(op, aid) for aid, _ in arrays(mix) for op in mix["ops"]]
    if mix.get("shuffle", True):
        order = _rng(seed, 1, r).permutation(len(pairs))
        pairs = [pairs[i] for i in order]
    return pairs


# --------------------------------------------------------------- requests


def _round_lengths(mix: dict) -> list:
    """Prompt lengths of one round, in the exact proportions."""
    n = int(mix["round"])
    counts = [w * n for w in mix["prompt_weights"]]
    if any(abs(c - round(c)) > 1e-9 for c in counts) \
            or round(sum(counts)) != n:
        raise ValueError(f"round of {n} cannot hold weights "
                         f"{mix['prompt_weights']} exactly")
    out = []
    for b, c in zip(mix["prompt_buckets"], counts):
        out.extend([int(b)] * int(round(c)))
    return out


def _round_budgets(mix: dict) -> list:
    """``max_new`` of one round: the distribution's quantiles at
    (i + 1/2) / round, clipped and rounded."""
    spec = mix["max_new"]
    n = int(mix["round"])
    if spec["dist"] != "lognormal":
        raise ValueError(f"max_new dist {spec['dist']!r}")
    dist = statistics.NormalDist(np.log(spec["median"]), spec["sigma"])
    out = []
    for i in range(n):
        v = float(np.exp(dist.inv_cdf((i + 0.5) / n)))
        out.append(int(min(max(round(v), spec["min"]), spec["max"])))
    return out


def requests(mix: dict, seed: int, vocab: int, *, rounds=None,
             start_uid: int = 0) -> list:
    """The queue: ``[{"uid", "prompt", "max_new"}]``, greedy requests
    ready for ``ContinuousServer.serve``."""
    if mix.get("sampling", "greedy") != "greedy":
        raise ValueError("only greedy traffic can be checked token by "
                         "token against the reference")
    lens, budgets = _round_lengths(mix), _round_budgets(mix)
    # One fixed pairing of lengths with budgets, and one fixed order of
    # each round, for every seed; the seed only draws the tokens.
    pairs = [(lens[i], budgets[j]) for i, j in enumerate(
        np.random.default_rng(0).permutation(len(budgets)))]
    rounds = int(mix["rounds"] if rounds is None else rounds)
    out = []
    for r in range(rounds):
        for k in _rng(0, 2, r).permutation(len(pairs)):
            length, budget = pairs[k]
            uid = start_uid + len(out)
            out.append({
                "uid": uid,
                "prompt": _rng(seed, 3, uid).integers(0, vocab, length,
                                                      dtype=np.int32),
                "max_new": budget,
            })
    return out


def warm_requests(mix: dict, seed: int, vocab: int) -> list:
    """Requests that take every shape the mix uses: each prompt
    bucket, and enough of them that every slot decodes."""
    buckets = [int(b) for b in mix["prompt_buckets"]]
    n = max(int(mix["slots"]), len(buckets))
    rng = _rng(seed, 4)
    return [{"uid": -1 - i,
             "prompt": rng.integers(0, vocab, buckets[i % len(buckets)],
                                    dtype=np.int32),
             "max_new": 3} for i in range(n)]
