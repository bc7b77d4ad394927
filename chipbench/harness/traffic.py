"""Seeded traffic from a mix file: sessions, lengths, arrivals, tokens.

A mix is a JSON file under ``chipbench/traffic/`` that this one generator
reads.  A cell that needs other traffic adds a mix file and no code.
Keys (token counts throughout):

* ``arrival``: how sessions start.

  - ``{"kind": "poisson", "rate_per_s": r}``: open loop, sessions due on
    a schedule whatever the server does;
  - ``{"kind": "bursty", "rate_per_s": r, "burst": b, "peak_x": x}``:
    open loop, ON-OFF: bursts of ``b`` sessions arriving at ``x * r``,
    with silences between bursts that keep the mean rate at ``r``;
  - an open loop may add ``"block": m``: each span (warm-up, window,
    drain) is cut into blocks of about ``m`` sessions, each over its
    share of the span with a multiset of lengths and gaps of its own, so
    that the seed reorders work within a block and not across the whole
    window;
  - ``{"kind": "closed", "clients": c, "pool_per_client": k, "block":
    m}``: ``c`` callers, each starting its next session as soon as its
    last one finished (``k`` sessions prepared per caller).  The pool is
    sent in blocks of ``m`` sessions (default: the whole pool), each block
    the same multiset in a seeded order, so that the part of the pool a
    run reaches is much the same work whatever the seed.
* ``warm_s`` (default 0): seconds of load before the window opens.  An
  open loop's arrivals start that long before; a closed loop's window
  opens that long after every caller has its first token.
* ``prompt`` / ``output``: a length distribution, one of
  ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` (sigma of
  the underlying normal, clipped), ``{"dist": "fixed", "value"}``, or
  ``{"dist": "mixture", "parts": [{"weight": w, ...a distribution}]}``.
* ``session`` (optional): ``{"turns": <distribution>, "think_s": t}``.
  A session is that many requests; each next turn is sent ``t`` seconds
  after the last one finished, and its prompt is the previous turn's
  prompt, the tokens served for it, and a new ``prompt`` of its own.
  Without it every session is one request.
* ``prefix`` (optional): ``{"groups": g, "len": n}``: every session's
  first prompt starts with one of ``g`` shared prefixes of ``n`` tokens,
  the groups in equal shares.
* ``tenants`` (optional): ``{"n": n, "zipf_s": s}``: every session names
  a LoRA tenant (``Request.adapter_id``), tenant ``i`` with a share
  proportional to ``1 / (i + 1) ** s``.
* ``content`` (optional): ``{"kind": "uniform"}`` (the default: token
  ids uniform over the vocabulary) or ``{"kind": "repeat", "period": p}``
  (each prompt repeats a block of ``p`` ids drawn from the seed).

Every seed gets the same work in another order: the same multiset of
lengths, turns, tenants, prefix groups and inter-arrival gaps.  Lengths
are a distribution's quantiles at ``(i + 0.5) / n`` (a mixture splits
``n`` over its parts by weight), and gaps the exponential's, paired once
by a fixed permutation and then shuffled by the seed.  So two seeds
differ only in order, which keeps seed-to-seed spread to the scheduling
effects the benchmark is meant to see.  The token ids are drawn from the
seed.

The length and arrival arithmetic is a copy of the program's
``repro.traffic.arrivals`` (``LengthDist`` lognormal, unit-rate Poisson
gaps scaled by ``1 / rate``, ON-OFF bursts), kept here so the yardstick
cannot move with the program.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List, Optional, Sequence

import numpy as np

#: fixed permutation seed pairing the quantiles of different draws, the
#: same for every run seed (part of the mix's definition, not of a run)
PAIRING_SEED = 20240717


def _largest_remainder(n: int, weights: Sequence[float]) -> np.ndarray:
    """Split ``n`` into whole counts proportional to ``weights``."""
    w = np.asarray(weights, np.float64)
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    order = np.argsort(-(exact - counts), kind="stable")
    counts[order[:n - counts.sum()]] += 1
    return counts


@dataclasses.dataclass(frozen=True)
class LengthDist:
    """Lognormal token count: median, sigma of log, clipped to [lo, hi]."""
    median: float
    sigma: float
    lo: int
    hi: int

    def quantiles(self, n: int) -> np.ndarray:
        """The ``n`` lengths at quantiles ``(i + 0.5) / n``, ascending."""
        z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
        x = np.rint(self.median * np.exp(self.sigma * z)).astype(np.int64)
        return np.clip(x, self.lo, self.hi)


def _dist_quantiles(d: Dict, n: int) -> np.ndarray:
    kind = d.get("dist")
    if kind == "lognormal":
        ld = LengthDist(float(d["median"]), float(d["sigma"]), int(d["min"]),
                        int(d["max"]))
        if not (1 <= ld.lo <= ld.hi and ld.median >= 1 and ld.sigma >= 0):
            raise ValueError(f"bad length dist {d!r}")
        return ld.quantiles(n)
    if kind == "fixed":
        if int(d["value"]) < 1:
            raise ValueError(f"bad length dist {d!r}")
        return np.full(n, int(d["value"]), np.int64)
    if kind == "mixture":
        parts = d["parts"]
        counts = _largest_remainder(n, [float(p["weight"]) for p in parts])
        out = [_dist_quantiles(p, int(c)) for p, c in zip(parts, counts)]
        return np.sort(np.concatenate(out)) if out else np.zeros(0, np.int64)
    raise ValueError(f"length dist must be lognormal, fixed or mixture, "
                     f"got {d!r}")


def dist_max(d: Dict) -> int:
    """The largest length a distribution can give."""
    kind = d.get("dist")
    if kind == "lognormal":
        return int(d["max"])
    if kind == "fixed":
        return int(d["value"])
    if kind == "mixture":
        return max(dist_max(p) for p in d["parts"])
    raise ValueError(f"unknown length dist {d!r}")


def lengths(d: Dict, n: int, stream: int) -> np.ndarray:
    """``n`` lengths of distribution ``d`` in a fixed order of their own
    (``stream`` keeps the orders of different draws apart)."""
    q = _dist_quantiles(d, n)
    return q[np.random.default_rng([PAIRING_SEED, stream]).permutation(n)]


def exp_quantile_gaps(n: int, rate: float) -> np.ndarray:
    """Poisson inter-arrival gaps at ``rate``: exponential quantiles."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


@dataclasses.dataclass
class GenRequest:
    """One turn of a session: its new prompt tokens and output budget."""
    idx: int
    prompt: np.ndarray          # int32 ids: a first turn's whole prompt,
                                # a later turn's new tokens
    max_new: int
    session: int = 0
    turn: int = 0
    adapter_id: Optional[int] = None


@dataclasses.dataclass
class Session:
    turns: List[GenRequest]
    due_s: float = 0.0          # open loop: after the load starts


@dataclasses.dataclass
class Traffic:
    loop: str                   # open | closed
    sessions: List[Session]     # open: in due order; closed: send order
    clients: int = 0
    warm_s: float = 0.0         # load before the window opens
    think_s: float = 0.0        # between a session's turns

    @property
    def requests(self) -> List[GenRequest]:
        return [g for s in self.sessions for g in s.turns]


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


def _open_dues(arr: Dict, span: float, n: int,
               order: np.random.Generator) -> np.ndarray:
    """``n`` due times spread over ``span`` seconds, in a seeded order of
    a fixed multiset of gaps (or of bursts)."""
    rate = float(arr["rate_per_s"])
    kind = arr["kind"]
    if kind == "poisson":
        gaps = exp_quantile_gaps(n, rate)
        gaps *= span / gaps.sum()
        return np.concatenate([[0.0], np.cumsum(gaps[order.permutation(n)])
                               [:-1]])
    if kind == "bursty":
        b, x = int(arr["burst"]), float(arr["peak_x"])
        if b < 1 or x < 1:
            raise ValueError(f"bad bursty arrival {arr!r}")
        n_b = -(-n // b)
        sizes = _largest_remainder(n, [1.0] * n_b)[order.permutation(n_b)]
        inner = exp_quantile_gaps(n - n_b, x * rate)
        inner = inner[order.permutation(n - n_b)]
        off = max(span - float(inner.sum()), 0.0) / n_b
        dues, t, k = [], 0.0, 0
        for s in sizes:
            for j in range(int(s)):
                if j:
                    t += inner[k]
                    k += 1
                dues.append(t)
            t += off                # silence before the next burst
        return np.asarray(dues)
    raise ValueError(f"open-loop arrival must be poisson or bursty, "
                     f"got {arr!r}")


def _sessions(mix: Dict, n: int, order: np.random.Generator,
              toks: np.random.Generator, vocab: int, first_idx: int,
              first_session: int, prefixes: List[np.ndarray]
              ) -> List[Session]:
    """``n`` sessions in a seeded order of a fixed multiset."""
    sess = mix.get("session")
    turns = (lengths(sess["turns"], n, 2) if sess else np.ones(n, np.int64))
    n_req = int(turns.sum())
    p = lengths(mix["prompt"], n_req, 0)
    o = lengths(mix["output"], n_req, 1)
    ten = mix.get("tenants")
    tenant = None
    if ten:
        share = 1.0 / (np.arange(int(ten["n"])) + 1.0) ** float(ten["zipf_s"])
        tenant = np.repeat(np.arange(int(ten["n"])),
                           _largest_remainder(n, share))
        tenant = tenant[np.random.default_rng([PAIRING_SEED, 3])
                        .permutation(n)]
    group = None
    if prefixes:
        group = np.arange(n) % len(prefixes)
    content = mix.get("content", {"kind": "uniform"})

    def ids(k: int) -> np.ndarray:
        if content["kind"] == "uniform":
            return toks.integers(0, vocab, k, dtype=np.int32)
        if content["kind"] == "repeat":
            base = toks.integers(0, vocab, int(content["period"]),
                                 dtype=np.int32)
            return np.resize(base, k)
        raise ValueError(f"content must be uniform or repeat: {content!r}")

    # the sessions are made in a fixed order, then sent in a seeded one
    out, k = [], 0
    for j in range(n):
        ts = []
        for t in range(int(turns[j])):
            new = ids(int(p[k]))
            if t == 0 and group is not None:
                new = np.concatenate([prefixes[group[j]], new])
            ts.append(GenRequest(
                0, new, int(o[k]), turn=t,
                adapter_id=None if tenant is None else int(tenant[j])))
            k += 1
        out.append(Session(ts))
    out = [out[j] for j in order.permutation(n)]
    idx = first_idx
    for j, s in enumerate(out):
        for g in s.turns:
            g.idx, g.session = idx, first_session + j
            idx += 1
    return out


def max_context(mix: Dict) -> int:
    """The most positions one session can reach."""
    pre = mix.get("prefix")
    turns = dist_max(mix["session"]["turns"]) if mix.get("session") else 1
    return ((int(pre["len"]) if pre else 0)
            + turns * (dist_max(mix["prompt"]) + dist_max(mix["output"])))


def generate(mix: Dict, seed: int, window_s: float, vocab: int,
             drain_s: float, max_len: Optional[int] = None) -> Traffic:
    """The run's sessions: those of the warm-up, of the window, and of
    the drain that keeps the load on while the window's last requests
    finish (``drain_s``).  ``max_len``, when given, is the engine's
    longest sequence: a mix that could pass it is refused."""
    if max_len is not None and max_context(mix) > max_len:
        raise ValueError(f"mix reaches {max_context(mix)} positions, past "
                         f"the engine's max_len {max_len}")
    arr = mix["arrival"]
    order = _rng(seed, 0)
    toks = _rng(seed, 1)
    pre = mix.get("prefix")
    prefixes = [toks.integers(0, vocab, int(pre["len"]), dtype=np.int32)
                for _ in range(int(pre["groups"]))] if pre else []
    warm = float(mix.get("warm_s", 0.0))
    think = float(mix["session"].get("think_s", 0.0)) if mix.get(
        "session") else 0.0
    if arr["kind"] == "closed":
        clients = int(arr["clients"])
        n = clients * int(arr["pool_per_client"])
        block = int(arr.get("block", n))
        if block < 1 or n % block:
            raise ValueError(f"block must divide the pool of {n}: {arr!r}")
        ss: List[Session] = []
        for _ in range(n // block):
            ss += _sessions(mix, block, order, toks, vocab,
                            sum(len(s.turns) for s in ss), len(ss), prefixes)
        return Traffic("closed", ss, clients=clients, warm_s=warm,
                       think_s=think)
    # the warm-up's, the window's and the drain's sessions, each block a
    # fixed multiset in a seeded order spread over its share of the span
    out: List[Session] = []
    t0 = 0.0
    for span in (warm, window_s, drain_s):
        n = int(math.ceil(float(arr["rate_per_s"]) * span))
        if n == 0:
            continue
        n_blocks = max(1, round(n / int(arr["block"]))) if "block" in arr \
            else 1
        t = t0
        for m in _largest_remainder(n, [1.0] * n_blocks):
            sub = span * int(m) / n
            ss = _sessions(mix, int(m), order, toks, vocab,
                           sum(len(s.turns) for s in out), len(out),
                           prefixes)
            for s, d in zip(ss, _open_dues(arr, sub, int(m), order)):
                s.due_s = t + float(d)
            out.extend(ss)
            t += sub
        t0 += span
    return Traffic("open", out, warm_s=warm, think_s=think)


def percentile(xs: Sequence[float], q: float) -> float:
    """Linear-interpolation percentile (numpy's default), as the program's
    ``repro.traffic.slo`` computes it."""
    if not len(xs):
        return float("nan")
    return float(np.percentile(np.asarray(xs, np.float64), q))
