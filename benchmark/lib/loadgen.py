"""One general traffic generator, driven by a cell's ``traffic`` parameters.

A schedule is made from the seed alone. Every seed gets the same multiset of
sizes and the same arrival offsets, so that runs with different seeds do the
same amount of work; the seed decides the tokens of the prompts.

Traffic parameters (all in the cell's file):

  rate_per_s      mean arrivals a second (open loop)
  arrivals        "poisson", the one kind there is
  preroll_s       traffic that runs before the window opens (set-up); the
                  schedule's clock starts there
  horizon_s       the schedule is made for this long and cut to the window
  count_block_s   the arrivals are a Poisson process conditioned on its
                  count: the pre-roll, and then every stretch of this many
                  seconds, holds exactly rate x its length arrivals (the
                  fraction is carried over), at uniform times inside it. So
                  the window holds the rate the cell states, whatever the
                  draw. Without the key the whole horizon is one stretch
  prompt / answer {"dist": "lognormal", "median", "sigma", "min", "max"} or
                  {"dist": "uniform", "min", "max"} or {"dist": "fixed", "value"}
  schedule_seed   seed of the multiset itself (fixed in the cell)
"""
import numpy as np


def _sizes(rng, spec, n):
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if dist == "uniform":
        return rng.integers(int(spec["min"]), int(spec["max"]) + 1, n)
    if dist == "lognormal":
        x = rng.lognormal(np.log(float(spec["median"])), float(spec["sigma"]),
                          n)
        return np.clip(np.rint(x), int(spec["min"]),
                       int(spec["max"])).astype(np.int64)
    raise ValueError("unknown size distribution %r" % (dist,))


def _arrivals(rng, traffic):
    rate, horizon = float(traffic["rate_per_s"]), float(traffic["horizon_s"])
    if traffic.get("arrivals", "poisson") != "poisson":
        raise ValueError("unknown kind of arrivals %r" % traffic["arrivals"])
    block = float(traffic.get("count_block_s", horizon))
    edges = [0.0]
    if 0.0 < float(traffic.get("preroll_s", 0.0)) < horizon:
        edges.append(float(traffic["preroll_s"]))
    while horizon - edges[-1] > 1e-9:
        edges.append(min(horizon, edges[-1] + block))
    due, owed = [], 0.0
    for lo, hi in zip(edges[:-1], edges[1:]):
        owed += rate * (hi - lo)
        n = int(owed + 1e-9)
        owed -= n
        due.append(rng.uniform(lo, hi, n))
    return np.sort(np.concatenate(due))


def base_schedule(traffic):
    """The multiset every seed shares: arrival offsets (sorted, seconds from
    the start of the pre-roll) and the (prompt, answer) size pairs, from
    ``schedule_seed``."""
    rng = np.random.default_rng(int(traffic.get("schedule_seed", 0)))
    due = _arrivals(rng, traffic)
    return due, _sizes(rng, traffic["prompt"], len(due)), \
        _sizes(rng, traffic["answer"], len(due))


def schedule(traffic, seed, vocab, window_s):
    """Requests due inside ``window_s`` (pre-roll included): a list of dicts
    with ``due_s``, ``prompt`` (int32 array), ``max_new_tokens``, in due
    order."""
    due, plen, alen = base_schedule(traffic)
    rng = np.random.default_rng([int(seed), 0x10ad])
    out = []
    for i in range(len(due)):
        if due[i] >= window_s:
            break
        out.append({"due_s": float(due[i]),
                    "prompt": rng.integers(0, vocab, int(plen[i])
                                           ).astype(np.int32),
                    "max_new_tokens": int(alen[i])})
    return out


def train_batches(traffic, seed, vocab, count):
    """``count`` distinct host batches of BERT pretraining rows, all rows
    different: token ids, token types, valid lengths, masked positions,
    masked labels, next-sentence labels."""
    rng = np.random.default_rng([int(seed), 0xba7c])
    b, t, p = int(traffic["batch"]), int(traffic["seq"]), int(traffic["masked"])
    out = []
    for _ in range(count):
        out.append((
            rng.integers(0, vocab, (b, t)).astype(np.int32),
            rng.integers(0, 2, (b, t)).astype(np.int32),
            np.full((b,), t, np.float32),
            np.stack([rng.choice(t, p, replace=False) for _ in range(b)]
                     ).astype(np.int32),
            rng.integers(0, vocab, (b, p)).astype(np.int32),
            rng.integers(0, 2, (b,)).astype(np.int32)))
    return out
