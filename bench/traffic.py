"""The one traffic generator: a mix file's parameters in, requests out.

Arrivals are an open-loop Poisson process per request class, conditioned
on its count: ``round(rate * seconds)`` arrival times drawn uniformly over
the window. The times and the insert/update/delete make-up of every
mutation request come from the mix's own ``arrival_seed``, so every run
seed offers the same arrivals and the same request sizes; the run seed
permutes the make-ups and draws the content (which points, which
features). A mutation request never touches one id twice, so the order of
its rows does not matter.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.corpus import rng_for

INSERT, UPDATE, DELETE = 0, 1, 2


@dataclasses.dataclass(frozen=True)
class Arrival:
    t: float                     # seconds after the window opens
    kind: str                    # "query" | "mutate"
    make_up: tuple = ()          # mutation row kinds, in row order


def class_rates(mix: dict, ops_per_s: float) -> tuple[float, float]:
    """(query requests/s, mutation requests/s) for a total of ``ops_per_s``
    rows per second split by the mix's ``query_share``."""
    share = float(mix["query_share"])
    return (ops_per_s * share / int(mix["query_rows"]),
            ops_per_s * (1.0 - share) / int(mix["mutation_rows"]))


def schedule(mix: dict, ops_per_s: float, seconds: float, seed: int,
             phase: int = 0) -> list[Arrival]:
    """Arrivals of one window, sorted by time. ``phase`` picks another
    fixed arrival set (warm-up uses 1, the window 0)."""
    fixed = np.random.default_rng([int(mix["arrival_seed"]), phase])
    q_rate, m_rate = class_rates(mix, ops_per_s)
    n_q, n_m = round(q_rate * seconds), round(m_rate * seconds)
    t_q = fixed.uniform(0.0, seconds, n_q)
    t_m = fixed.uniform(0.0, seconds, n_m)
    fracs = np.asarray([mix["insert"], mix["update"], mix["delete"]], float)
    rows = int(mix["mutation_rows"])
    make_ups = fixed.choice(3, size=(n_m, rows), p=fracs / fracs.sum())
    make_ups = make_ups[rng_for(seed, 10 + phase).permutation(n_m)]
    out = [Arrival(float(t), "query") for t in t_q]
    out += [Arrival(float(t), "mutate", tuple(int(x) for x in mu))
            for t, mu in zip(t_m, make_ups)]
    out.sort(key=lambda a: (a.t, a.kind))
    return out


class ContentStream:
    """Draws request content over the live set, mutation by mutation.

    Inserts take held-back corpus points (then fresh ids); updates and
    deletes pick live points. Upserted dense features carry ``jitter``
    Gaussian noise per coordinate, as the program's own stream does."""

    def __init__(self, ids: np.ndarray, feats: dict, n_boot: int, seed: int,
                 jitter: float):
        self.feats = feats
        self.n = len(ids)
        self.pending = list(ids[n_boot:].tolist())
        self.live = list(ids[:n_boot].tolist())
        self.pos = {pid: i for i, pid in enumerate(self.live)}
        self.rng = rng_for(seed, 1)
        self.next_fresh = int(ids.max()) + 1
        self.jitter = jitter

    def _add(self, pid: int) -> None:
        self.pos[pid] = len(self.live)
        self.live.append(pid)

    def _remove(self, pid: int) -> None:
        i = self.pos.pop(pid)
        last = self.live.pop()
        if last != pid:
            self.live[i] = last
            self.pos[last] = i

    def _pick(self, taken: set) -> int:
        while True:
            pid = self.live[int(self.rng.integers(len(self.live)))]
            if pid not in taken:
                return pid

    def features_of(self, ids, jitter: float = 0.0) -> dict:
        ids = np.asarray(ids, np.int64)
        out = {k: np.array(v[ids % self.n]) for k, v in self.feats.items()}
        if jitter > 0:
            for k in out:
                if k.startswith("dense:"):
                    out[k] = out[k] + (jitter * self.rng.normal(
                        size=out[k].shape)).astype(np.float32)
        return out

    def mutation(self, make_up) -> tuple[np.ndarray, np.ndarray, dict]:
        """(kinds int32 [R], ids int64 [R], features of every row)."""
        kinds, ids, taken = [], [], set()
        for kind in make_up:
            if kind != INSERT and len(self.live) - len(taken) < 4:
                kind = INSERT
            if kind == INSERT:
                pid = self.pending.pop() if self.pending else self.next_fresh
                if pid == self.next_fresh:
                    self.next_fresh += 1
                self._add(pid)
            else:
                pid = self._pick(taken)
                if kind == DELETE:
                    self._remove(pid)
            taken.add(pid)
            kinds.append(kind)
            ids.append(pid)
        ids = np.asarray(ids, np.int64)
        return (np.asarray(kinds, np.int32), ids,
                self.features_of(ids, self.jitter))

    def query(self, rows: int = 1) -> dict:
        """Features of ``rows`` live points, as the program serves reads."""
        pick = self.rng.integers(0, len(self.live), rows)
        return self.features_of([self.live[int(i)] for i in pick])
