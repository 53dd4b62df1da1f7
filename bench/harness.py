"""Set-up, warm-up and the open-loop window through the served front end.

The entry the window drives is ``repro.serve.frontend.Frontend`` over
``GusEngine(EngineConfig(pipeline=True))`` over ``DynamicGUS`` on the
sharded backend, configured by ``launch.serve.gus_config`` as served.
Everything here runs in one thread: arrivals due by now are admitted with
their scheduled arrival time, then the front end runs one step; latency
therefore counts every wait a stall imposes on later requests.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import time

import numpy as np

from bench.corpus import make_corpus, rng_for, take
from bench.traffic import ContentStream, schedule

CLOCK = time.perf_counter
DRAIN_LIMIT_S = 60.0       # how long past the close answers are awaited
WINDOW_SPAN = "bench.window"


# program counters whose change a slow step reports, to tell a stall of
# the host from work the program chose to do in that step
STEP_COUNTERS = ("engine_snapshots_total", "index_compactions_total",
                 "index_slab_grows_total", "index_resplits_total",
                 "pipeline_windows_total")


class GcClock:
    """Seconds the interpreter spent collecting garbage, and how many
    full (generation 2) collections it made."""

    def __init__(self):
        self.seconds = 0.0
        self.full = 0
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.seconds += time.perf_counter() - self._t
            self.full += info["generation"] == 2


GC = GcClock()


def percentile(values, q: float) -> float | None:
    """Linear-interpolated percentile over every value (None if empty)."""
    return float(np.percentile(np.asarray(values, float), q)) \
        if len(values) else None


class Annotations:
    """Host spans in the profiler's trace, named from the benchmark's side
    (``bench.step``, ``bench.submit_query`` ...); free when not tracing."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)


def scorer_weights(cfg: dict, seed: int):
    """Random scorer MLP weights from the seed, made on the device in one
    jitted call, in float32 as served: He-normal matrices, zero biases."""
    import jax
    import jax.numpy as jnp

    dims = ([2 * len(cfg["dense"]) + 2 * len(cfg["sets"])
             + len(cfg["scalars"])] + [cfg["scorer"]["hidden"]]
            * cfg["scorer"]["layers"] + [1])

    def make(key):
        out = {}
        for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
            key, sub = jax.random.split(key)
            out[f"w{i}"] = jax.random.normal(sub, (a, b), jnp.float32) \
                * np.float32((2.0 / a) ** 0.5)
            out[f"b{i}"] = jnp.zeros((b,), jnp.float32)
        return out

    words = np.random.SeedSequence(int(seed) % 2**63).generate_state(2)
    return jax.jit(make)(jnp.asarray(words, jnp.uint32))


@dataclasses.dataclass
class Deployment:
    cfg: dict
    engine: object
    fe: object
    content: ContentStream
    boot_ids: np.ndarray
    boot_feats: dict
    params: dict                  # scorer weights, host copies
    k: int
    phases: dict                  # set-up phase -> seconds
    dispatched: list = dataclasses.field(default_factory=list)
    base_applied: int = 0         # engine batches applied before us


def build(cell, seed: int) -> Deployment:
    """Corpus, scorer weights, index build and graph seeding."""
    import jax
    from repro.core import BucketConfig, DynamicGUS
    from repro.core.types import FeatureSpec
    from repro.graph import GraphConfig
    from repro.launch.serve import gus_config
    from repro.serve.engine import EngineConfig, GusEngine
    from repro.serve.frontend import Frontend

    cfg, phases = cell.config, {}
    t = CLOCK()
    ids, feats = make_corpus(cfg, seed)
    n_boot = int(len(ids) * float(cfg["bootstrap_fraction"]))
    phases["dataset_s"] = CLOCK() - t
    t = CLOCK()
    params = jax.block_until_ready(scorer_weights(cfg, seed))
    phases["scorer_init_s"] = CLOCK() - t
    spec = FeatureSpec(dense=dict(cfg["dense"]), sets=dict(cfg["sets"]),
                       scalars=tuple(cfg["scalars"]))
    b = cfg["buckets"]
    bcfg = BucketConfig(dense_tables=b["dense_tables"],
                        dense_bits=b["dense_bits"],
                        set_tables=b["set_tables"],
                        scalar_widths=tuple(b["scalar_widths"]),
                        seed=b["seed"])
    graph = GraphConfig(k=cfg["graph_k"]) if cfg["graph_k"] else None
    gus = DynamicGUS(spec, bcfg, params, gus_config(
        len(ids), scann_nn=cfg["scann_nn"], backend="sharded",
        shards=cfg["shards"], graph=graph))
    boot_ids, boot_feats = ids[:n_boot], take(feats, slice(0, n_boot))
    t = CLOCK()
    gus.bootstrap(boot_ids, boot_feats)
    total = CLOCK() - t
    seeding = sum(gus.graph_timer.samples_ms) / 1e3
    phases["index_build_s"] = total - seeding
    phases["graph_seeding_s"] = seeding
    engine = GusEngine(gus, EngineConfig(pipeline=True))
    return Deployment(
        cfg=cfg, engine=engine, fe=Frontend(engine),
        content=ContentStream(ids, feats, n_boot, seed,
                              float(cell.mix["jitter"])),
        boot_ids=boot_ids, boot_feats=boot_feats,
        params={k: np.asarray(v) for k, v in params.items()},
        k=int(cell.mix["k"]), phases=phases,
        base_applied=gus.seq_applied)


class Window:
    """What one stretch of open-loop traffic did, request by request."""

    def __init__(self, dep: Deployment, ann: Annotations,
                 spans: bool = False):
        self.dep = dep
        self.ann = ann
        # with spans on, each step runs inside a trace of its own, so the
        # write path's spans (encode, handoff) are kept when a mutation,
        # not a query, set them off
        self.spans = spans
        self.clock = dep.fe.clock    # the front end's clock times latency
        self.sleep = time.sleep
        self.queries = {}        # rid -> dict(due, latency_ms, result, ...)
        self.mutations = {}      # rid -> dict(due, index, visible_ms)
        self.failed = 0
        self.attempted = 0
        self.lateness_ms = []
        self.steps = []          # (seconds, mutations, queries, changes)
        self.deadline = float("inf")   # answers after it came too late
        self._unseen = []        # dispatch indices not yet visible

    # ------------------------------------------------------------ admit
    def submit_query(self, due: float, feats: dict) -> None:
        self.attempted += 1
        with self.ann("bench.submit_query"):
            resp = self.dep.fe.submit_query(feats, k=self.dep.k,
                                            arrival_s=due)
        if resp.status == "accepted":
            self.queries[resp.rid] = {"due": due, "feats": feats}
        else:
            self.failed += 1

    def submit_mutation(self, due: float, make_up) -> None:
        from repro.core.types import MutationBatch
        self.attempted += 1
        kinds, ids, feats = self.dep.content.mutation(make_up)
        batch = MutationBatch(kinds=kinds, ids=ids, features=feats)
        with self.ann("bench.submit_mutation"):
            resp = self.dep.fe.submit_mutation(batch, arrival_s=due)
        if resp.status == "accepted":
            self.mutations[resp.rid] = {"due": due, "batch": batch}
        else:
            # a shed request never reaches the engine: the content stream
            # drew it, so the live set the stream believes in may now be
            # ahead of the engine's; the reference replays only what was
            # dispatched, as the engine applies it
            self.failed += 1

    # ------------------------------------------------------------- step
    def step(self) -> None:
        tracer = self.dep.engine.obs.tracer
        fe = self.dep.fe
        depth = fe.queue_depth("mutate"), fe.queue_depth("query")
        marks = self.marks()
        t = self.clock()
        with self.ann("bench.step"):
            if self.spans:
                tr = tracer.trace("bench")
                with tracer.activate(tr):
                    out = self.dep.fe.step()
                tracer.collect(tr)
            else:
                out = self.dep.fe.step()
        t_end = self.clock()
        changed = {k: round(v - marks[k], 4)
                   for k, v in self.marks().items() if v != marks[k]}
        self.steps.append((t_end - t, depth[0] - fe.queue_depth("mutate"),
                           depth[1] - fe.queue_depth("query"), changed))
        self.settle(out, t_end)

    def marks(self) -> dict:
        reg = self.dep.engine.obs.registry
        out = {"gc_s": GC.seconds, "gc_full": GC.full}
        for name in STEP_COUNTERS:
            inst = reg.get(name)
            if inst is not None:
                out[name] = inst.value
        return out

    def step_summary(self) -> str:
        """Steps taken, their median and the slowest five, with the
        mutation and query requests each dispatched and the program
        counters and garbage collection that changed in it."""
        if not self.steps:
            return "no steps"
        secs = [s[0] for s in self.steps]
        slow = sorted(self.steps, key=lambda s: s[0], reverse=True)[:5]
        return (f"{len(secs)} steps, median {percentile(secs, 50):.4f} s, "
                "slowest (s, mutations, queries, changes) "
                + ", ".join(f"({s:.3f}, {m}, {q}, {c})"
                            for s, m, q, c in slow))

    def settle(self, out, t_end: float) -> None:
        """Record a step's terminal responses, then every mutation whose
        batch the engine has applied (``seq_applied`` counts batches
        through a hand-off, in dispatch order) becomes visible at
        ``t_end``."""
        dep = self.dep
        for r in out:
            if r.kind == "mutate":
                rec = self.mutations.get(r.rid)
                if rec is None:
                    continue
                rec["late"] = t_end > self.deadline
                if r.status == "ok":
                    rec["index"] = len(dep.dispatched)
                    dep.dispatched.append(rec["batch"])
                    self._unseen.append(rec)
                else:
                    self.failed += 1
                    rec["error"] = r.status
            else:
                rec = self.queries.get(r.rid)
                if rec is None:
                    continue
                rec["late"] = t_end > self.deadline
                if r.status == "ok":
                    rec.update(latency_ms=r.latency_ms, result=r.result,
                               applied=len(dep.dispatched))
                else:
                    self.failed += 1
                    rec["error"] = r.status
        applied = visible_through(dep.engine.gus.seq_applied,
                                  dep.base_applied)
        keep = []
        for rec in self._unseen:
            if rec["index"] < applied:
                rec["visible_ms"] = (t_end - rec["due"]) * 1e3
            else:
                keep.append(rec)
        self._unseen = keep

    def busy(self) -> bool:
        fe = self.dep.fe
        return bool(fe.queue_depth("query") or fe.queue_depth("mutate"))

    # ------------------------------------------------------------ loop
    def run(self, arrivals, query_rows: int) -> float:
        """Offer ``arrivals`` open loop from now, then step until every
        accepted request is answered (at most ``DRAIN_LIMIT_S`` past the
        last arrival); returns the window's opening time on the front
        end's clock."""
        clock = self.clock
        t0 = clock()
        self.deadline = t0 + (arrivals[-1].t if arrivals else 0) \
            + DRAIN_LIMIT_S
        i = 0
        while True:
            now = clock()
            while i < len(arrivals) and t0 + arrivals[i].t <= now:
                a = arrivals[i]
                self.lateness_ms.append((now - t0 - a.t) * 1e3)
                if a.kind == "query":
                    self.submit_query(t0 + a.t,
                                      self.dep.content.query(query_rows))
                else:
                    self.submit_mutation(t0 + a.t, a.make_up)
                i += 1
            if self.busy():
                self.step()
            elif i < len(arrivals):
                wait = t0 + arrivals[i].t - clock()
                if wait > 0:
                    with self.ann("bench.idle"):
                        self.sleep(wait)
            else:
                break
            if clock() > self.deadline:
                break
        # what is still queued past the limit is answered now, so that the
        # reference replays every batch the engine applied, but is lost
        while self.busy():
            self.step()
        return t0

    def flush(self) -> None:
        tracer = self.dep.engine.obs.tracer
        with self.ann("bench.flush"):
            if self.spans:
                tr = tracer.trace("bench")
                with tracer.activate(tr):
                    self.dep.engine.flush()
                tracer.collect(tr)
            else:
                self.dep.engine.flush()
        self.settle([], self.clock())

    # --------------------------------------------------------- results
    def lost(self) -> int:
        """Accepted requests with no terminal response within
        ``DRAIN_LIMIT_S`` of the last arrival."""
        return sum(rec.get("late", True) and "error" not in rec
                   for recs in (self.queries, self.mutations)
                   for rec in recs.values())

    def query_latencies(self) -> list:
        return [q["latency_ms"] for q in self.queries.values()
                if "latency_ms" in q]

    def visible_latencies(self) -> list:
        return [m["visible_ms"] for m in self.mutations.values()
                if "visible_ms" in m]

    def answered(self) -> list:
        return [q for q in self.queries.values() if "result" in q]


def visible_through(seq_applied: int, base: int) -> int:
    """Dispatched batches that have gone through a hand-off: the engine
    applies batches in dispatch order, so it is the first
    ``seq_applied - base`` of them."""
    return max(int(seq_applied) - int(base), 0)


def warm_up(dep: Deployment, cell, seed: int, seconds: float) -> "Window":
    """Compile what the window will run: every query group size the
    front end fuses; every upsert count the write path can encode, which
    is a request's own (1 to ``mutation_rows``; a request with deletes
    closes the pipeline's fuse window and applies alone) or that of a
    fused window of 2 up to the pipeline's window of whole delete-free
    requests; then the cell's own mix for ``seconds``. Returns the
    warm-up's window."""
    from bench.traffic import INSERT
    w = Window(dep, Annotations(False))
    fe_cfg = dep.fe.cfg
    rows = int(cell.mix["mutation_rows"])
    now = w.clock
    for g in range(1, fe_cfg.query_dispatch + 1):
        for _ in range(g):
            w.submit_query(now(), dep.content.query(1))
        w.step()
    window = max((p.window_size() for p in dep.engine.pipelines), default=1)
    for n in [*range(1, rows + 1), *(rows * k for k in range(2, window + 1))]:
        for done in range(0, n, rows):
            w.submit_mutation(now(), (INSERT,) * min(rows, n - done))
        while w.busy():          # no query yet, so the requests fuse
            w.step()
        w.submit_query(now(), dep.content.query(1))
        while w.busy():
            w.step()
    w.run(schedule(cell.mix, float(cell.rate["ops_per_s"]), seconds, seed,
                   phase=1), int(cell.mix["query_rows"]))
    w.flush()
    return w


def probe_queries(dep: Deployment, n: int, seed: int) -> list:
    """``n`` seeded queries on the quiesced corpus, through the front
    end; returns their records (features and result)."""
    w = Window(dep, Annotations(False))
    rng = rng_for(seed, 20)
    for _ in range(n):
        pid = dep.content.live[int(rng.integers(len(dep.content.live)))]
        w.submit_query(w.clock(), dep.content.features_of([pid]))
        if dep.fe.queue_depth("query") >= dep.fe.cfg.query_queue:
            while w.busy():      # the front end would shed the next one
                w.step()
    while w.busy():
        w.step()
    return w.answered()
