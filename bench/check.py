"""The comparison that decides ``correct``, and the recall of the probes.

Numbers compared, each against the limit in the configuration file:

* ``lost``: accepted requests that got no terminal answer within a minute
  of the close (an answer that comes late is late, not wrong).
* ``dead_ids``: served ids, and maintained-graph edge ends, that were not
  live when the answer was computed: a deleted point served.
* ``dist_mismatches``: served (query, id) pairs whose distance lies
  outside the reference's count of shared buckets between the query and
  the id's current features (a SimHash table with a projection near zero
  may count either way, see ``reference``): a stale or altered answer,
  or an index row not updated.
* ``weight_gap``: the widest gap between a served edge weight (answers and
  graph edges) and the float64 reference scorer on current features.
* ``graph_miss`` (maintained graph only): the share of a live point's
  exact top-k neighbours (tie-tolerant, by shared buckets, as recall
  counts them) that its graph row lacks, over sampled points inserted by
  the stream and, apart, over sampled bootstrapped points: the worse of
  the two.

The control is put in the program's place: every served weight is
replaced by the reference scorer with its matmuls at a lower pass
(``reference.mlp_lower``, bfloat16 operands), on the same pairs, and
judged as the program's would be; it must fail ``weight_gap``.
"""
from __future__ import annotations

import numpy as np

from bench import reference as ref
from bench.corpus import rng_for

SAMPLE = 256          # window answers compared per run
EDGE_SAMPLE = 2048    # maintained-graph edges compared per run
ROW_SAMPLE = 128      # graph rows compared per run, of each kind


class Checker:
    """``control``: the reference with its matmuls at that lower pass
    (``"bfloat16"``, the control; ``"high"``, a reading) in the program's
    place. ``swap_rows``: every graph row handed to another live point
    before it is compared, the fault that sets ``graph_miss``'s upper
    reading."""

    def __init__(self, dep, control: str | None = None,
                 swap_rows: bool = False):
        self.cfg = dep.cfg
        self.params = dep.params
        self.corpus = ref.Corpus(dep.boot_ids, dep.boot_feats)
        self.dispatched = dep.dispatched
        self.planes = ref.hyperplanes(dep.cfg)
        self.rounding = ref.simhash_rounding(dep.cfg)
        self.r = {name: 0 for name in dep.cfg["limits"]}
        self.info = {"pairs": 0, "uncertain": 0, "edges": 0}
        self.control = control
        self.swap_rows = swap_rows
        self.mismatched = []     # details of the first mismatched pairs
        self._index = None

    def advance(self, applied: int) -> None:
        while self.corpus.applied < applied:
            b = self.dispatched[self.corpus.applied]
            self.corpus.apply(b.kinds, b.ids, b.features)

    def _weights(self, fa, fb, served) -> None:
        x = ref.pair_signals(self.cfg, fa, fb)
        want = ref.mlp(self.params, x)
        if self.control:
            served = ref.mlp_lower(self.params, x, self.control)
        served = np.asarray(served, np.float64)
        self.r["weight_gap"] = max(self.r["weight_gap"],
                                   float(np.abs(served - want).max()))

    def answer(self, q: dict) -> None:
        """One served answer, against the corpus as it stood when the
        engine computed it."""
        self.advance(q["applied"])
        res = q["result"]
        ids = np.asarray(res.ids).reshape(-1)
        w = np.asarray(res.weights).reshape(-1)
        d = np.asarray(res.distances).reshape(-1)
        keep = ids >= 0
        live = np.asarray([self.corpus.live(p) for p in ids[keep]], bool)
        self.r["dead_ids"] += int((~live).sum())
        ids, w, d = ids[keep][live], w[keep][live], d[keep][live]
        if not ids.size:
            return
        cand = self.corpus.features(ids)
        qf = {k: np.repeat(np.asarray(v)[:1], ids.size, axis=0)
              for k, v in q["feats"].items()}
        self._weights(qf, cand, w)
        qb, qv, qu = ref.buckets(self.cfg, self.planes,
                                 {k: v[:1] for k, v in qf.items()},
                                 self.rounding)
        cb, cv, cu = ref.buckets(self.cfg, self.planes, cand, self.rounding)
        lo, hi = ref.shared_range(qb[0], qv[0], qu[0], cb, cv, cu)
        self.info["pairs"] += ids.size
        self.info["uncertain"] += int((hi > lo).sum())
        bad = (-d < lo - 0.5) | (-d > hi + 0.5)
        self.r["dist_mismatches"] += int(bad.sum())
        if bad.any() and len(self.mismatched) < 8:
            # what the next reader needs to tell a sign flip near zero
            # from a stale or altered row
            near = ref.min_projection(self.cfg, self.planes,
                                      {k: v[bad] for k, v in cand.items()},
                                      self.rounding)
            qnear = ref.min_projection(self.cfg, self.planes,
                                       {k: v[:1] for k, v in qf.items()},
                                       self.rounding)[0]
            self.mismatched += [
                {"id": int(i), "served_shared": -float(a),
                 "shared_min": int(a0), "shared_max": int(b0),
                 "applied": int(q["applied"]), "query_margin": float(qnear),
                 "point_margin": float(m)}
                for i, a, a0, b0, m in zip(ids[bad], d[bad], lo[bad],
                                           hi[bad], near)]

    def edges(self, pairs: np.ndarray, weights: np.ndarray, seed: int):
        """Maintained-graph edges after the final flush: live ends,
        weights, and the rows' membership (``graph_miss``)."""
        self.advance(len(self.dispatched))
        pick = rng_for(seed, 30).permutation(len(pairs))[:EDGE_SAMPLE]
        sub, w = pairs[pick], weights[pick]
        live = np.asarray([self.corpus.live(a) and self.corpus.live(b)
                           for a, b in sub.tolist()], bool)
        self.r["dead_ids"] += int((~live).sum())
        sub, w = sub[live], w[live]
        self.info["edges"] += len(sub)
        if len(sub):
            self._weights(self.corpus.features(sub[:, 0]),
                          self.corpus.features(sub[:, 1]), w)
        self._rows(pairs, seed)

    def _rows(self, pairs: np.ndarray, seed: int) -> None:
        """``graph_miss``: sampled live points, half of them inserted by
        the stream and half bootstrapped, against their exact top-k."""
        k = int(self.cfg["graph_k"])
        row = {}
        for a, b in pairs.tolist():
            row.setdefault(a, set()).add(b)
            row.setdefault(b, set()).add(a)
        live = set(self.corpus.live_ids().tolist())
        inserted = sorted({int(p) for b in self.dispatched
                           for kind, p in zip(np.asarray(b.kinds).tolist(),
                                              np.asarray(b.ids).tolist())
                           if kind == 0} & live)
        rest = sorted(live - set(inserted))
        rng = rng_for(seed, 32)
        if self.swap_rows:
            order = sorted(live)
            other = rng_for(seed, 33).permutation(order).tolist()
            row = {p: row.get(q, set()) for p, q in zip(order, other)}
        shares, sampled = [], 0
        for group in (inserted, rest):
            points = rng.permutation(group)[:ROW_SAMPLE].tolist()
            if not points:
                continue
            missed = 0
            for p in points:
                counts = self._shared_counts(self.corpus.features([p]))
                counts[self._index[1][p]] = -1           # the point itself
                kth = np.sort(counts)[-k]
                nbrs = [self._index[1][q] for q in row.get(p, ())
                        if q in live]
                missed += k - min(int((counts[nbrs] >= kth).sum()), k)
            shares.append(missed / (k * len(points)))
            sampled += len(points)
        self.info["rows"] = sampled
        self.r["graph_miss"] = max(shares, default=0.0)

    def _shared_counts(self, feats: dict) -> np.ndarray:
        """Buckets the one point ``feats`` shares with each live point,
        over the final corpus (in ``live_ids`` order)."""
        if self._index is None:
            live = self.corpus.live_ids()
            cb, cv, _ = ref.buckets(self.cfg, self.planes,
                                    self.corpus.features(live),
                                    self.rounding)
            row, col = np.nonzero(cv)
            key = np.unique((row.astype(np.uint64) << np.uint64(32))
                            | cb[row, col].astype(np.uint64))
            rows = (key >> np.uint64(32)).astype(np.int64)
            bks = (key & np.uint64(0xFFFFFFFF)).astype(np.uint32)
            order = np.argsort(bks, kind="stable")
            self._index = ((rows[order], bks[order]),
                           {int(p): i for i, p in enumerate(live.tolist())},
                           len(live))
        (rows, bks), _, n = self._index
        qb, qv, _ = ref.buckets(self.cfg, self.planes, feats, self.rounding)
        counts = np.zeros(n, np.int64)
        for b in set(qb[0][qv[0]].tolist()):
            lo = np.searchsorted(bks, np.uint32(b), "left")
            hi = np.searchsorted(bks, np.uint32(b), "right")
            counts[rows[lo:hi]] += 1
        return counts

    def recall(self, probes: list, k: int) -> float:
        """Tie-tolerant recall@k of the probes against exact kNN over the
        live corpus: a served id counts when it shares at least as many
        buckets with the query as the k-th best live point does."""
        self.advance(len(self.dispatched))
        good = 0
        for q in probes:
            counts = self._shared_counts(q["feats"])
            pos = self._index[1]
            kth = np.sort(counts)[-k] if len(counts) >= k else 0
            for pid in np.asarray(q["result"].ids).reshape(-1).tolist():
                if pid >= 0 and pid in pos and counts[pos[pid]] >= kth:
                    good += 1
        return good / max(len(probes) * k, 1)


def sample(answered: list, seed: int, n: int = SAMPLE) -> list:
    """A seeded sample of window answers, in the order they were served."""
    pick = rng_for(seed, 31).permutation(len(answered))[:n]
    return sorted((answered[i] for i in pick), key=lambda q: q["applied"])


def judge(readings: dict, limits: dict) -> bool:
    return all(readings[name] <= limit for name, limit in limits.items())
