"""Continuous-batching traversal serving over a shared ``AdvancePlan`` pair.

``bfs_multi`` vmaps *identical* queries; real traffic is a continuous
stream of heterogeneous ones — mixed BFS / SSSP / PageRank, arbitrary
sources, staggered arrival and completion.  This module is the serving
tier that sits on top of the load-balancing layer (the ROADMAP's
millions-of-users scenario): one :class:`GraphServer` holds a single plan
pair built once per graph, a :class:`QueryBatch` of fixed lane width ``W``
carries per-lane traversal state, and one jitted step advances every live
lane together.  Converged lanes retire and queued queries backfill the
freed lanes **without re-tracing** — lane lifecycle is data (masks and
selects), never shape.

Design (the espnet ``batch_beam_search_online`` pattern, applied to
traversal):

* **Unified lane state.**  BFS is unit-weight Bellman–Ford, so BFS and
  SSSP lanes share one min-combiner relax whose per-atom weight is a
  per-lane select between ``1.0`` and the plan's edge weight — one vmapped
  advance serves both kinds at no extra cost.  PageRank lanes ride a
  separate sum-combiner advance (the driver's power-iteration body) that
  runs under a *scalar* ``lax.cond`` — a stream with no live PageRank lane
  never pays it (and vice versa for the relax).  The lanes share one
  kernel launch (lanes are its leading grid axis), and every per-edge
  array of the step is ``[lanes, E]`` with the lanes leading: per-edge
  gathers go through :func:`repro.core.execute.lane_take`, since JAX's own
  vmap of a gather puts the lanes minor, which XLA:TPU pads 16-fold.  Each
  lane's ``[V]`` value row is its tentative distances (BFS/SSSP) or rank
  vector (PageRank).
* **Driver-exact recurrences.**  Each lane replays the exact loop body of
  its single-query driver (:func:`repro.sparse.graph.bfs` / ``sssp`` /
  ``pagerank``) over the same plan, so a retired lane's answer is
  **bitwise-identical** to the single-query result — the per-query drivers
  are the ``W=1`` special case of this layer.
* **Per-lane direction choice** falls out of the existing measured-density
  carry: each lane carries its frontier's active out-edge count, compared
  against the plan's modeled threshold.  Under vmap the direction
  ``lax.cond`` lowers to a both-branch select (the :func:`bfs_multi`
  caveat), so the server defaults to ``direction="pull"`` for throughput;
  ``"auto"`` stays available where per-lane adaptivity matters more than
  the double advance.
* **No-retrace contract.**  The step and admit functions are traced
  exactly once per server (pinned by :attr:`GraphServer.step_traces` /
  :attr:`GraphServer.admit_traces`); admission, retirement and backfill
  only change array *contents*.

See docs/serving.md for the lane lifecycle and the throughput-vs-latency
tradeoffs.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Deque, Dict, List, NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import ExecutionPath, Schedule, telemetry
from repro.core.execute import lane_take
from repro.sparse.advance import (AdvancePlan, advance, advance_push,
                                  build_advance)
from repro.sparse.graph import (Graph, INF, _active_edge_count, _directed,
                                _pagerank_share, _pagerank_update,
                                _validate_sources)

__all__ = ["KIND_BFS", "KIND_SSSP", "KIND_PAGERANK", "QueryBatch",
           "ServedResult", "GraphServer"]

KIND_BFS = 0
KIND_SSSP = 1
KIND_PAGERANK = 2

_KIND_CODES = {"bfs": KIND_BFS, "sssp": KIND_SSSP, "pagerank": KIND_PAGERANK}
_KIND_NAMES = {v: k for k, v in _KIND_CODES.items()}


class QueryBatch(NamedTuple):
    """Fixed-width lane state: every field is ``[W]`` or ``[W, V]``.

    A NamedTuple pytree so the whole batch flows through one jitted step.
    ``value`` is the unified per-lane answer row — tentative distances for
    BFS/SSSP lanes (``inf`` = unreached; BFS depths are the integer-valued
    distances of the unit-weight relax), the rank vector for PageRank.
    ``active`` marks occupied lanes, ``done`` marks converged lanes
    awaiting host retirement (their rows are frozen by the step's
    liveness select).  ``active_edges`` is the measured frontier out-edge
    count — the same carry the single-query drivers thread for the
    ``"auto"`` direction switch.  ``delta`` is the PageRank L1 step
    change; ``pushes`` counts push-direction advances per lane (the
    direction-statistics evidence, as in the drivers).
    """

    kind: jax.Array          # [W] int32 — KIND_BFS / KIND_SSSP / KIND_PAGERANK
    source: jax.Array        # [W] int32 (ignored by PageRank lanes)
    qid: jax.Array           # [W] int32 (-1 = free lane)
    active: jax.Array        # [W] bool
    done: jax.Array          # [W] bool
    iters: jax.Array         # [W] int32
    value: jax.Array         # [W, V] float32
    frontier: jax.Array      # [W, V] bool (BFS/SSSP lanes)
    active_edges: jax.Array  # [W] int32 — measured-density carry
    delta: jax.Array         # [W] float32 — PageRank L1 step change
    pushes: jax.Array        # [W] int32 — push-direction advance count


@dataclasses.dataclass(frozen=True)
class ServedResult:
    """One retired query: the answer plus serving metadata."""

    qid: int
    kind: str                # "bfs" | "sssp" | "pagerank"
    source: int
    value: np.ndarray        # bfs: int32 depths; sssp/pagerank: float32 [V]
    iterations: int          # traversal iterations the lane ran
    pushes: int              # push-direction advances the lane ran
    submitted_at: float      # perf_counter timestamps
    admitted_at: float
    completed_at: float

    @property
    def latency(self) -> float:
        """Submit-to-completion wall-clock seconds (queueing included)."""
        return self.completed_at - self.submitted_at


@dataclasses.dataclass
class _Pending:
    kind_code: int
    source: int
    submitted_at: float
    admitted_at: float = 0.0


class GraphServer:
    """Continuous-batching server for graph queries over one plan pair.

    Parameters mirror the single-query drivers: ``schedule="auto"`` routes
    the plan choice through the autotuner's ``"advance_serve"`` workload
    family (its own cache namespace; pass ``measure=`` under
    ``REPRO_AUTOTUNE_MEASURE=1`` for measured-mode selection on the
    serving relax), ``direction`` picks the advance orientation for
    BFS/SSSP lanes (``"pull"`` default — see the module docstring),
    ``max_iters``/``damping``/``num_iters``/``tol`` pin the per-kind
    convergence rules (defaults match the drivers: ``max_iters=V``,
    PageRank ``damping=0.85, num_iters=50, tol=0.0``).

    Host API::

        srv = GraphServer(graph, lanes=8)
        qid = srv.submit("bfs", source=3)
        results = srv.drain()          # or: srv.tick() per arrival slot

    ``submit`` may be called at any time — including between ticks while
    earlier queries are in flight — which is the continuous-batching
    contract.
    """

    def __init__(self, graph: Graph, *, lanes: int = 8,
                 plan: Optional[AdvancePlan] = None,
                 schedule: Schedule | str = "auto",
                 num_blocks: Optional[int] = None,
                 path: ExecutionPath | str = ExecutionPath.AUTO,
                 direction: str = "pull",
                 max_iters: Optional[int] = None,
                 damping: float = 0.85, num_iters: int = 50,
                 tol: float = 0.0,
                 measure=None):
        if graph.num_vertices == 0:
            raise ValueError("GraphServer needs a non-empty graph "
                             "(no valid query sources on 0 vertices)")
        if lanes < 1:
            raise ValueError(f"lane width must be >= 1, got {lanes}")
        if direction not in ("pull", "push", "auto"):
            raise ValueError(f"unknown direction: {direction!r} "
                             f"(expected 'pull', 'push' or 'auto')")
        self.graph = graph
        self.lanes = int(lanes)
        self.direction = direction
        self.plan = plan if plan is not None else build_advance(
            graph, schedule=schedule, num_blocks=num_blocks, path=path,
            workload="advance_serve", measure=measure)
        V = graph.num_vertices
        self._V = V
        self.max_iters = V if max_iters is None else int(max_iters)
        self.damping = float(damping)
        self.num_iters = int(num_iters)
        self.tol = float(tol)

        # -- host bookkeeping ---------------------------------------------
        self._queue: Deque[int] = deque()          # qids awaiting a lane
        self._pending: Dict[int, _Pending] = {}    # qid -> submit metadata
        self._lane_qid = np.full(self.lanes, -1, np.int64)  # host mirror
        self._next_qid = 0
        self.steps = 0            # serving steps executed
        self.served = 0           # queries retired
        self._step_traces = 0     # counted at trace time
        self._admit_traces = 0

        self.batch = self._empty_batch()
        self._jstep = jax.jit(self._make_step())
        self._jadmit = jax.jit(self._make_admit())

    # -- construction helpers ---------------------------------------------

    def _empty_batch(self) -> QueryBatch:
        W, V = self.lanes, self._V
        return QueryBatch(
            kind=jnp.zeros((W,), jnp.int32),
            source=jnp.zeros((W,), jnp.int32),
            qid=jnp.full((W,), -1, jnp.int32),
            active=jnp.zeros((W,), bool),
            done=jnp.zeros((W,), bool),
            iters=jnp.zeros((W,), jnp.int32),
            value=jnp.zeros((W, V), jnp.float32),
            frontier=jnp.zeros((W, V), bool),
            active_edges=jnp.zeros((W,), jnp.int32),
            delta=jnp.full((W,), INF, jnp.float32),
            pushes=jnp.zeros((W,), jnp.int32))

    def _make_step(self):
        direction = self.direction
        max_iters, num_iters = self.max_iters, self.num_iters
        damping, tol = self.damping, self.tol

        # The plan is the step's argument, not a closure: closed over by
        # jax.jit, its arrays would be compiled into the step as constants.
        def step(plan: AdvancePlan, b: QueryBatch) -> QueryBatch:
            self._step_traces += 1
            W, V = b.value.shape
            outdeg = plan.out_degrees.astype(jnp.float32)
            src, psrc = plan.src, plan.push_src
            w_pull, w_push = plan.weight, plan.push_weight

            def lane_relax(value, frontier, unit, active_edges):
                # One BFS/SSSP lane: the drivers' `_relax_directed` body with a
                # per-lane unit-weight select (BFS == unit-weight Bellman-Ford,
                # so SSSP lanes see exactly `value[src[e]] + weight[e]` — the
                # same two f32 operands, same rounding, as advance_relax_min).
                wl = jnp.where(unit, jnp.float32(1.0), w_pull)
                wp = jnp.where(unit, jnp.float32(1.0), w_push)
                cand, used_push = _directed(
                    plan, direction, active_edges,
                    lambda: advance_push(plan, frontier,
                                         lane_take(value, psrc) + wp,
                                         combiner="min"),
                    lambda: advance(plan, frontier,
                                    lane_take(value, src) + wl,
                                    combiner="min"))
                new_value = jnp.minimum(value, cand)
                return new_value, new_value < value, used_push

            def lane_pagerank(pr):
                # One PageRank lane: the driver's power-iteration body, pull
                # direction (the driver's "auto" resolution on a full
                # frontier), bit-for-bit.  The shared helpers pin per-op
                # rounding behind optimization barriers — without them XLA
                # fuses the update differently in the vmapped serving step
                # than in the driver's while_loop and the bits drift.
                share = _pagerank_share(pr, outdeg)
                contrib = advance(plan, None, lane_take(share, src),
                                  combiner="sum")
                dangling = jnp.sum(jnp.where(outdeg > 0, 0.0, pr))
                new_pr = _pagerank_update(contrib, dangling, damping, V)
                return new_pr, jnp.abs(new_pr - pr).sum()

            live = jnp.logical_and(b.active, ~b.done)
            is_pr = b.kind == KIND_PAGERANK
            dist_live = jnp.logical_and(live, ~is_pr)
            pr_live = jnp.logical_and(live, is_pr)
            unit = b.kind == KIND_BFS

            # BFS/SSSP relax — scalar-guarded: a PageRank-only step never
            # pays the vmapped min-advance (and vice versa below).  The
            # frontier mask already zeroes non-dist lanes, so masked lanes
            # relax against the min identity and stay put.
            f_eff = jnp.logical_and(b.frontier, dist_live[:, None])

            def run_dist(_):
                return jax.vmap(lane_relax)(b.value, f_eff, unit,
                                            b.active_edges)

            def skip_dist(_):
                return (b.value, jnp.zeros((W, V), bool),
                        jnp.zeros((W,), bool))

            d_value, d_frontier, used_push = jax.lax.cond(
                dist_live.any(), run_dist, skip_dist, operand=None)

            # PageRank power iteration — non-PR rows masked to zero so the
            # (discarded) lanes never mix distances (inf) into the sums.
            pr_in = jnp.where(pr_live[:, None], b.value, 0.0)

            def run_pr(_):
                return jax.vmap(lane_pagerank)(pr_in)

            def skip_pr(_):
                return b.value, b.delta

            p_value, p_delta = jax.lax.cond(pr_live.any(), run_pr, skip_pr,
                                            operand=None)

            # Merge per kind; freeze every non-live lane bit-for-bit.
            stepped = jnp.where(is_pr[:, None], p_value, d_value)
            new_value = jnp.where(live[:, None], stepped, b.value)
            new_frontier = jnp.where(dist_live[:, None], d_frontier,
                                     b.frontier)
            new_delta = jnp.where(pr_live, p_delta, b.delta)
            new_iters = b.iters + live.astype(jnp.int32)
            # the measured-density carry feeds the per-lane push/pull
            # switch; a static direction never reads it, so skip the
            # per-lane masked O(E) reduction (the drivers do the same)
            if direction == "auto":
                counts = jax.vmap(
                    lambda f: _active_edge_count(plan, f))(new_frontier)
                new_edges = jnp.where(dist_live, counts, b.active_edges)
            else:
                new_edges = b.active_edges

            # Convergence — exactly the drivers' while-loop negations:
            # BFS/SSSP run while (i < max_iters) & frontier.any();
            # PageRank while (i < num_iters) & (delta > tol).
            dist_done = jnp.logical_and(
                dist_live,
                jnp.logical_or(~d_frontier.any(axis=1),
                               new_iters >= max_iters))
            pr_done = jnp.logical_and(
                pr_live,
                jnp.logical_or(p_delta <= tol, new_iters >= num_iters))
            new_done = b.done | dist_done | pr_done
            new_pushes = b.pushes + jnp.logical_and(
                used_push, dist_live).astype(jnp.int32)
            return b._replace(done=new_done, iters=new_iters,
                              value=new_value, frontier=new_frontier,
                              active_edges=new_edges, delta=new_delta,
                              pushes=new_pushes)

        return step

    def _make_admit(self):
        V = self._V

        def admit(plan: AdvancePlan, b: QueryBatch, clear, take, kind,
                  source, qid) -> QueryBatch:
            # clear: [W] bool — retired lanes to free; take: [W] bool —
            # lanes to (re)initialize from kind/source/qid.  Pure content
            # writes: the batch's shapes never change, so the serving step
            # never re-traces across retire/backfill boundaries.
            self._admit_traces += 1
            ids = jnp.arange(V, dtype=jnp.int32)
            is_pr = kind == KIND_PAGERANK
            f0 = jnp.logical_and(ids[None, :] == source[:, None],
                                 ~is_pr[:, None])
            dist0 = jnp.where(f0, 0.0, INF)
            pr0 = jnp.full((self.lanes, V), 1.0 / V, jnp.float32)
            value0 = jnp.where(is_pr[:, None], pr0, dist0)
            if self.direction == "auto":
                counts0 = jax.vmap(
                    lambda f: _active_edge_count(plan, f))(f0)
            else:    # static direction: the density carry is never read
                counts0 = jnp.zeros((self.lanes,), jnp.int32)

            sel = lambda m, new, old: jnp.where(m, new, old)
            selv = lambda m, new, old: jnp.where(m[:, None], new, old)
            zero = jnp.zeros((self.lanes,), jnp.int32)
            return QueryBatch(
                kind=sel(take, kind, b.kind),
                source=sel(take, source, b.source),
                qid=sel(take, qid, sel(clear, -1, b.qid)),
                active=jnp.logical_or(
                    jnp.logical_and(b.active, ~clear), take),
                done=jnp.logical_and(b.done, ~(clear | take)),
                iters=sel(take, zero, b.iters),
                value=selv(take, value0, b.value),
                frontier=selv(take, f0, b.frontier),
                active_edges=sel(take, counts0, b.active_edges),
                delta=sel(take, jnp.full_like(b.delta, INF), b.delta),
                pushes=sel(take, zero, b.pushes))

        return admit

    # -- trace counters (the no-retrace contract) --------------------------

    @property
    def step_traces(self) -> int:
        """Times the serving step has been traced (must stay 1)."""
        return self._step_traces

    @property
    def admit_traces(self) -> int:
        """Times the admit function has been traced (must stay 1)."""
        return self._admit_traces

    # -- host-side serving loop -------------------------------------------

    @property
    def queued(self) -> int:
        """Queries waiting for a lane."""
        return len(self._queue)

    @property
    def in_flight(self) -> int:
        """Queries occupying lanes (running or awaiting retirement)."""
        return int((self._lane_qid >= 0).sum())

    def submit(self, kind: str, source: int = 0) -> int:
        """Enqueue one query; returns its qid.  Callable at any time."""
        if kind not in _KIND_CODES:
            raise ValueError(f"unknown query kind: {kind!r} "
                             f"(expected one of {sorted(_KIND_CODES)})")
        if kind != "pagerank":
            _validate_sources(source, self._V,
                              what=f"{kind} query source")
        qid = self._next_qid
        self._next_qid += 1
        self._pending[qid] = _Pending(_KIND_CODES[kind], int(source),
                                      time.perf_counter())
        self._queue.append(qid)
        return qid

    @telemetry.span("serve.retire")
    def _retire(self) -> List[ServedResult]:
        """Read converged lanes off the device and free them (host side)."""
        occupied = self._lane_qid >= 0
        if not occupied.any():
            return []
        done = np.asarray(self.batch.done) & occupied
        if not done.any():
            return []
        values = np.asarray(self.batch.value)
        iters = np.asarray(self.batch.iters)
        pushes = np.asarray(self.batch.pushes)
        now = time.perf_counter()
        results = []
        for lane in np.nonzero(done)[0]:
            qid = int(self._lane_qid[lane])
            meta = self._pending.pop(qid)
            row = values[lane]
            if meta.kind_code == KIND_BFS:
                # integer-valued unit-weight distances -> the drivers'
                # int32 depth labels (-1 = unreached); exact below 2**24
                out = np.where(np.isfinite(row), row, -1.0).astype(np.int32)
            else:
                out = row.copy()
            results.append(ServedResult(
                qid=qid, kind=_KIND_NAMES[meta.kind_code],
                source=meta.source, value=out, iterations=int(iters[lane]),
                pushes=int(pushes[lane]), submitted_at=meta.submitted_at,
                admitted_at=meta.admitted_at, completed_at=now))
            self._lane_qid[lane] = -1
        self.served += len(results)
        self._retired_lanes = done   # handed to the next admit as `clear`
        return results

    @telemetry.span("serve.tick")
    def tick(self) -> List[ServedResult]:
        """One serving slot: retire converged lanes, backfill from the
        queue, advance every live lane one iteration.  Returns the queries
        retired this tick."""
        results = self._retire()
        clear = getattr(self, "_retired_lanes", None)
        if clear is None:
            clear = np.zeros(self.lanes, bool)
        self._retired_lanes = None

        free = np.nonzero(self._lane_qid < 0)[0]
        take = np.zeros(self.lanes, bool)
        kind = np.zeros(self.lanes, np.int32)
        source = np.zeros(self.lanes, np.int32)
        qid = np.zeros(self.lanes, np.int32)
        now = time.perf_counter()
        for lane in free:
            if not self._queue:
                break
            q = self._queue.popleft()
            meta = self._pending[q]
            meta.admitted_at = now
            take[lane] = True
            kind[lane] = meta.kind_code
            source[lane] = meta.source
            qid[lane] = q
            self._lane_qid[lane] = q

        if clear.any() or take.any():
            with telemetry.span("serve.admit"):
                self.batch = self._jadmit(
                    self.plan, self.batch, jnp.asarray(clear),
                    jnp.asarray(take), jnp.asarray(kind),
                    jnp.asarray(source), jnp.asarray(qid))
        if (self._lane_qid >= 0).any():
            with telemetry.span("serve.step"):
                self.batch = self._jstep(self.plan, self.batch)
            self.steps += 1
        return results

    def drain(self) -> List[ServedResult]:
        """Tick until the queue and every lane are empty; returns all
        queries retired during the drain, in retirement order."""
        results: List[ServedResult] = []
        while self._queue or (self._lane_qid >= 0).any():
            results.extend(self.tick())
        return results

    def serve(self, queries) -> Dict[int, ServedResult]:
        """Convenience one-shot: submit ``(kind, source)`` pairs (source
        optional for ``"pagerank"``), drain, return results by qid."""
        for q in queries:
            if isinstance(q, str):
                self.submit(q)
            else:
                self.submit(*q)
        return {r.qid: r for r in self.drain()}
