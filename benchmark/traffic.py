"""The one traffic generator: a traffic file's parameters plus a seed give
the requests, their sizes and, for an open loop, when each is due.

Lengths come from the statistic a public trace publishes (a mean or a
median), drawn from the exponential law with that centre: the sources
publish no spread, and the exponential is the law that a single centre
fixes.  ``scale`` shrinks them to fit the model's positions.  Every run
draws stratified quantiles, so it holds the stated mix exactly, and the
requests come in one fixed order whatever the seed: two seeds offer the
same work at the same times, with other prompt tokens (and, in the
harness, other weights).  So runs of different seeds spread no more than
runs of one seed.

Traffic file keys:

    source        the trace and the statistics taken from it
    loop          "open" (requests due on a schedule) | "closed" (clients
                  that each send their next request when the last ends)
    rate          open: requests per second offered (Poisson arrivals)
    workers       open: client connections, so the most requests in flight
    clients       closed: an integer, or "slots" for one per decode slot
    block         closed: each client's requests come in blocks that hold
                  the stated mix exactly
    scale         every length is the source's times this
    prompt_len    {"dist": "exponential", "mean" | "median": m,
                   "buckets": [...]}: each length goes to the nearest
                  bucket on a log scale (the program compiles prefill per
                  prompt length)
    output_len    {"dist": "exponential", "mean" | "median": m,
                   "min": a, "max": b}: rounded, then clipped to [a, b]
"""

from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

# a closed-loop client never needs more requests than this many a second
_CLOSED_MAX_PER_CLIENT_S = 8
# the order of sizes and arrival gaps, the same for every seed
_ORDER = np.random.SeedSequence([0x7eaf1c, 4])


def seed_seq(seed: int, stream: int) -> np.random.SeedSequence:
    """Independent streams of one run's randomness (weights 0, traffic 1,
    check sample 2, warm-up 3), valid for any whole-number seed."""
    return np.random.SeedSequence([int(seed) % (1 << 64), stream])


def _strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _exponential(spec: dict, scale: float, n: int) -> np.ndarray:
    """n stratified quantiles of the exponential law with the spec's mean
    or median, times `scale`."""
    if spec["dist"] != "exponential":
        raise ValueError(f"unknown length dist {spec['dist']!r}")
    mean = spec["mean"] if "mean" in spec else spec["median"] / math.log(2)
    return -np.log1p(-_strata(n)) * float(mean) * scale


def prompt_lengths(traffic: dict, n: int) -> List[int]:
    buckets = np.asarray(sorted(traffic["prompt_len"]["buckets"]), float)
    x = np.maximum(_exponential(traffic["prompt_len"],
                                traffic.get("scale", 1.0), n), 1.0)
    i = np.argmin(np.abs(np.log(x)[:, None] - np.log(buckets)[None]), 1)
    return [int(buckets[j]) for j in i]


def output_lengths(traffic: dict, n: int) -> List[int]:
    spec = traffic["output_len"]
    x = np.rint(_exponential(spec, traffic.get("scale", 1.0), n))
    return [int(v) for v in np.clip(x, spec["min"], spec["max"])]


def gaps(rate: float, n: int) -> np.ndarray:
    """n Poisson inter-arrival gaps of mean 1/rate."""
    return -np.log1p(-_strata(n)) / rate


def max_prompt(traffic: dict) -> int:
    return max(int(v) for v in traffic["prompt_len"]["buckets"])


def max_output(traffic: dict) -> int:
    return int(traffic["output_len"]["max"])


def clients(traffic: dict, slots: int) -> int:
    c = traffic["clients"]
    return slots if c == "slots" else int(c)


def max_outstanding(traffic: dict, slots: int) -> int:
    """The most requests the generator can have open at once."""
    if traffic["loop"] == "closed":
        return clients(traffic, slots)
    return int(traffic["workers"])


def _mix(traffic: dict, n: int, order: np.random.Generator):
    p = prompt_lengths(traffic, n)
    o = output_lengths(traffic, n)
    order.shuffle(p)
    order.shuffle(o)
    return p, o


def _request(rid: int, plen: int, olen: int, vocab: int,
             rng: np.random.Generator) -> Dict:
    return {"id": rid, "prompt": rng.integers(0, vocab, plen).tolist(),
            "max_new": int(olen)}


def schedule(traffic: dict, seed: int, seconds: float, vocab: int,
             slots: int) -> Dict:
    """The requests of one run.  Open loop: ``requests`` sorted by ``due``
    (seconds after the window opens); those due inside the window are
    ``counted``, and one more window of them keeps the load on while the
    counted ones finish.  Closed loop: ``clients``, one request list
    each."""
    rng = np.random.default_rng(seed_seq(seed, 1))
    order = np.random.default_rng(_ORDER)
    rid = 0
    if traffic["loop"] == "open":
        rate = float(traffic["rate"])
        n = max(1, round(rate * seconds))
        reqs = []
        for block in range(2):               # the window, then the drain
            g = gaps(rate, n)
            order.shuffle(g)
            g = g * (seconds / g.sum())
            due = np.concatenate([[0.0], np.cumsum(g)[:-1]])
            p, o = _mix(traffic, n, order)
            for i in range(n):
                r = _request(rid, p[i], o[i], vocab, rng)
                r["due"] = float(block * seconds + due[i])
                r["counted"] = block == 0
                reqs.append(r)
                rid += 1
        return {"loop": "open", "seconds": seconds, "requests": reqs,
                "workers": int(traffic["workers"])}
    if traffic["loop"] == "closed":
        block = int(traffic["block"])
        per_client = math.ceil(seconds * _CLOSED_MAX_PER_CLIENT_S / block)
        out = []
        for _ in range(clients(traffic, slots)):
            lst = []
            for _ in range(per_client):
                p, o = _mix(traffic, block, order)
                for i in range(block):
                    lst.append(_request(rid, p[i], o[i], vocab, rng))
                    rid += 1
            out.append(lst)
        return {"loop": "closed", "seconds": seconds, "clients": out}
    raise ValueError(f"unknown loop {traffic['loop']!r}")
