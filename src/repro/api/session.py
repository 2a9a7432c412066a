"""CrawlSession — the one driver API over the SPMD crawler.

Every entry point used to re-wire the same Phase II loop by hand: build a
mesh, call ``make_spmd_crawler``, alternate ``step_f``/``step_d`` on a
``(t + 1) % dispatch_interval`` modulo, harvest FetchReports to numpy. The
session owns that lifecycle once:

    sess = CrawlSession(cfg)              # mesh/context/state built here
    rep = sess.run(64)                    # N cycles -> typed CrawlReport
    sess.inject_failure(1); sess.heal()   # C4 controls
    sess.checkpoint(d); sess.restore(d)   # train/checkpoint.py hooks

Execution modes (DESIGN.md §11): the **eager** path steps one jitted
shard_map per cycle (exactly the old loop — one host round-trip per step);
the **scan** path (:meth:`run_chunk`) fuses a whole dispatch interval —
``dispatch_interval - 1`` fetch steps then the dispatch step — into a single
jitted ``lax.scan`` under the shard_map, so the host pays one round-trip per
interval instead of per step. ``CrawlState``/``FetchReport`` are NamedTuple
pytrees, which is what lets the scan carry the full crawl state and stack
the per-step reports. ``run(mode="auto")`` uses the scan path whenever the
step counter is interval-aligned and no event falls mid-interval; the two
paths produce bit-identical trajectories (tests/test_session.py).

``make_crawl_step``/``make_spmd_crawler`` (core/crawler.py) remain the
stable kernel-facing layer the session composes — custom stages and score
functions thread straight through.
"""
from __future__ import annotations

import math
import os
import time
from typing import Callable, Dict, Optional, Sequence, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.api.report import CrawlReport, harvest, stats_dict, stats_per_shard
from repro.compat import shard_map
from repro.configs.base import CrawlConfig
from repro.core import classifier as CLS
from repro.core import crawler as CR
from repro.core.stages import (CrawlState, FetchReport, init_state,
                               state_specs)

Events = Dict[int, Callable]   # step index -> state transform, applied BEFORE
                               # that step executes (session-absolute indices)

_OBS_DIR = "obs"               # ledger checkpoints live beside the crawl state


class CrawlSession:
    """Owns mesh, step functions, crawl state, and the step counter."""

    def __init__(self, cfg: CrawlConfig, mesh=None, *, axes=("data",),
                 score_fn: Optional[Callable] = None,
                 classify_accuracy: float = CLS.DEFAULT_ACCURACY,
                 stages: Optional[Sequence] = None,
                 extra_stages: Sequence = (),
                 dispatch_stage: Optional[Callable] = None,
                 tracer=None):
        """``score_fn`` (legacy ``(urls, cfg)``) overrides the ordering
        registry's scorer (default: ``cfg.ordering`` decides, DESIGN.md §12).
        ``extra_stages`` slots scenario stages (``make_politeness_stage``,
        ``make_revisit_stage``, ...) into the assembled pipeline by their
        ``placement`` attribute; ``stages`` replaces the whole pipeline
        verbatim (expert mode). ``tracer`` shares an ``obs.Tracer`` across
        sessions (ServeSession passes its own so crawl + serve spans land on
        one timeline)."""
        from repro import obs
        from repro.launch.mesh import make_host_mesh
        self.cfg = cfg
        self.mesh = mesh if mesh is not None else make_host_mesh()
        self.axes = axes if isinstance(axes, tuple) else (axes,)
        self.n_shards = int(math.prod(self.mesh.shape[a] for a in self.axes))
        self._kw = dict(score_fn=score_fn,
                        classify_accuracy=classify_accuracy)
        if stages is not None:
            self._kw["stages"] = stages
        if extra_stages:
            self._kw["extra_stages"] = tuple(extra_stages)
        if dispatch_stage is not None:
            self._kw["dispatch_stage"] = dispatch_stage
        self._init, self._step_f, self._step_d = CR.make_spmd_crawler(
            cfg, self.mesh, axes=self.axes, **self._kw)
        self.state: CrawlState = self._init()
        self._t = 0
        self._chunk_fn = None          # built lazily on first scan use
        # -- observability (DESIGN.md §17); off -> all hooks are dead code on
        # the step path and the compiled programs are the untraced ones
        self.telemetry = obs.telemetry_enabled(cfg)
        self.tracer = tracer if tracer is not None else obs.Tracer()
        self.ledger = (obs.LedgerBuffer(obs.ledger_metrics(cfg), self.n_shards)
                       if self.telemetry else None)
        self._snap_fn = None           # eager-path ledger snapshot, lazy
        # -- load-driven elastic repartitioning (DESIGN.md §18): a host-side
        # control-plane check at dispatch boundaries, like inject_failure/
        # heal. Disabled (threshold <= 0) means the hook is never consulted
        # and the trajectory is bit-identical to a build without it.
        self.rebalance_events: list = []
        self._rebalance = None
        if cfg.rebalance_threshold > 0:
            if not self.telemetry:
                raise ValueError(
                    "rebalance_threshold > 0 needs telemetry=True: the "
                    "trigger signal is the ledger's load-imbalance factor")
            from repro.rebalance import get_rebalance
            self._rebalance = get_rebalance(cfg.rebalance)

    # -- introspection ------------------------------------------------------

    @property
    def t(self) -> int:
        """Steps taken so far (mirrors ``state.step`` without a device sync)."""
        return self._t

    @property
    def stats(self) -> Dict[str, int]:
        return stats_dict(self.state)

    def reset(self) -> "CrawlSession":
        """Fresh crawl state + step counter 0, REUSING the compiled step
        functions — cheap repeated trajectories for sweeps and property
        tests (tests/test_invariants.py drives hundreds of schedules
        through one session per config)."""
        self.state = self._init()
        self._t = 0
        self.rebalance_events = []
        if self.telemetry:
            self.ledger.clear()
        return self

    # -- the two execution paths -------------------------------------------

    def step(self) -> FetchReport:
        """Advance ONE cycle eagerly; fetch vs dispatch is chosen internally
        from the step counter. Returns that step's FetchReport."""
        dispatch = (self._t + 1) % self.cfg.dispatch_interval == 0
        fn = self._step_d if dispatch else self._step_f
        if not self.telemetry:
            self.state, rep = fn(self.state)
            self._t += 1
            return rep
        name = "step_dispatch" if dispatch else "step_fetch"
        with self.tracer.span(name, "stage", t=self._t):
            self.state, rep = fn(self.state)
            row = np.asarray(self._snapshot()(
                self.state, jnp.float32(1.0 if dispatch else 0.0)))
            jax.block_until_ready(self.state)
        self._t += 1
        self.ledger.append(self._t, row)
        if dispatch:
            self._emit_counters()
            self.maybe_rebalance()
        return rep

    def run_chunk(self) -> FetchReport:
        """Advance one FUSED dispatch interval (the jitted scan core) and
        return the interval's stacked FetchReport (leading time axis).

        Requires the step counter to sit on an interval boundary so the
        chunk's final step is the dispatch step."""
        iv = self.cfg.dispatch_interval
        if self._t % iv:
            raise ValueError(
                f"run_chunk: step counter t={self._t} is not aligned to "
                f"dispatch_interval={iv}; use .step() to reach a boundary")
        if self._chunk_fn is None:
            self._chunk_fn = chunk_program(
                self.cfg, self.mesh, axes=self.axes,
                telemetry=self.telemetry, **self._kw)
        if not self.telemetry:
            self.state, reps = self._chunk_fn(self.state)
            self._t += iv
            return reps
        with self.tracer.span("run_chunk", "stage", t=self._t, interval=iv):
            self.state, reps, rows = self._chunk_fn(self.state)
            rows = np.asarray(rows)           # blocks on the chunk's result
            jax.block_until_ready(self.state)
        t0, self._t = self._t, self._t + iv
        self.ledger.append_block(range(t0 + 1, t0 + iv + 1), rows)
        self._emit_counters()
        self.maybe_rebalance()
        return reps

    # -- telemetry plumbing --------------------------------------------------

    def _snapshot(self):
        """The eager-path ledger snapshot: the SAME ``snapshot_local`` the
        scan path stacks, as its own jitted shard_map — identical HLO, so
        the eager and scan ledgers are bit-identical (tests/test_obs.py)."""
        if self._snap_fn is None:
            from repro.obs import ledger as OL
            cfg, axes = self.cfg, self.axes
            self._snap_fn = jax.jit(shard_map(
                lambda st, d: OL.snapshot_local(cfg, axes, st, dispatch=d),
                mesh=self.mesh,
                in_specs=(state_specs(axes), P()), out_specs=P(axes)))
        return self._snap_fn

    def _emit_counters(self) -> None:
        """Counter events at each dispatch boundary — the ledger tail as
        Chrome ``C`` rows (one series per shard)."""
        tail = self.ledger.tail()
        for metric in ("frontier_depth", "staging_fill"):
            if metric in tail:
                self.tracer.counter(metric, {
                    f"shard{i}": v for i, v in enumerate(tail[metric])})

    def telemetry_report(self, *, start: int = 0):
        """The session's :class:`~repro.obs.health.CrawlTelemetry` (ledger
        window from record ``start`` + every span so far); None when off."""
        if not self.telemetry:
            return None
        from repro.obs.health import CrawlTelemetry
        steps, rows = self.ledger.arrays()
        return CrawlTelemetry(steps=steps[start:], rows=rows[start:],
                              names=self.ledger.names,
                              interval=self.cfg.dispatch_interval,
                              spans=tuple(self.tracer.events))

    # -- the driver loop ----------------------------------------------------

    def run(self, steps: int, *, events: Optional[Events] = None,
            collect: str = "urls", mode: str = "auto") -> CrawlReport:
        """Drive ``steps`` cycles and return a :class:`CrawlReport`.

        events  — {step index: fn(state) -> state}, applied before that step
                  (indices are session-absolute, i.e. compared to ``self.t``).
        collect — "urls" (default: fetched URLs; C1/C2 overlap is computed
                  lazily on first ``report.overlap`` access) or "counts"
                  (per-step counts only; urls stays empty).
        mode    — "auto" fuses every interval the events/alignment allow,
                  "eager" forces per-step execution, "scan" demands full
                  fusion (raises if alignment or events make that impossible).
        """
        if mode not in ("auto", "eager", "scan"):
            raise ValueError(f"unknown mode {mode!r}")
        if collect not in ("urls", "counts"):
            raise ValueError(f"unknown collect {collect!r}")
        iv = self.cfg.dispatch_interval
        events = events or {}
        t_end = self._t + steps
        if mode == "scan":
            bad = self._t % iv or steps % iv or \
                any(e % iv for e in events if self._t <= e < t_end)
            if bad:
                raise ValueError(
                    "mode='scan' needs an interval-aligned start, an "
                    "interval-multiple step count, and no mid-interval "
                    f"events (t={self._t}, steps={steps}, interval={iv})")

        url_parts, per_step = [], []
        led0 = len(self.ledger) if self.telemetry else 0
        reb0 = len(self.rebalance_events)
        t0 = time.time()
        while self._t < t_end:
            t = self._t
            if t in events:
                self.state = events[t](self.state)
            fits = (t % iv == 0) and (t + iv <= t_end)
            clear = not any(t < e < t + iv for e in events)
            rep = (self.run_chunk() if mode != "eager" and fits and clear
                   else self.step())
            u, c = harvest(rep)
            per_step.extend(c)
            if collect == "urls":
                url_parts.extend(u)
        seconds = time.time() - t0

        urls = (np.concatenate(url_parts) if url_parts
                else np.array([], np.uint32))
        return CrawlReport(urls=urls,
                           per_step=np.asarray(per_step, np.int64),
                           stats=stats_dict(self.state), seconds=seconds,
                           cfg=self.cfg,
                           stats_per_shard=stats_per_shard(self.state),
                           telemetry=self.telemetry_report(start=led0),
                           rebalances=tuple(self.rebalance_events[reb0:]))

    # -- C4 fault controls --------------------------------------------------

    def inject_failure(self, shards: Union[int, Sequence[int]]) -> "CrawlSession":
        """Mark crawl process(es) dead (wraps ``crawler.mark_dead``)."""
        shards = [shards] if isinstance(shards, int) else list(shards)
        self.state = CR.mark_dead(self.state, shards)
        if self.telemetry:
            self.tracer.instant("inject_failure", "fault", t=self._t,
                                shards=list(shards))
        return self

    def heal(self, shards: Union[int, Sequence[int], None] = None
             ) -> "CrawlSession":
        """Rebalance dead shards' domains onto survivors (wraps
        ``train.fault.heal_crawler``). Defaults to every shard currently
        dead in ``state.shard_alive`` — the single source of truth, so it
        stays correct across events, checkpoints, and :meth:`restore`."""
        from repro.train.fault import heal_crawler
        if shards is None:
            shards = [int(s) for s in
                      np.flatnonzero(~np.asarray(self.state.shard_alive))]
        elif isinstance(shards, int):
            shards = [shards]
        else:
            shards = list(shards)
        if not shards:
            raise ValueError("heal: no dead shards in state and none given")
        self.state = heal_crawler(self.state, self.cfg, shards, self.n_shards)
        if self.telemetry:
            self.tracer.instant("heal", "fault", t=self._t,
                                shards=list(shards))
        return self

    # -- load-driven elastic repartitioning (DESIGN.md §18) ------------------

    def _windowed_imbalance(self) -> float:
        """The trigger signal: mean load-imbalance factor over the last
        ``cfg.rebalance_window`` dispatch-boundary ledger records."""
        from repro.obs.health import CrawlTelemetry
        steps, rows = self.ledger.arrays()
        tel = CrawlTelemetry(steps=steps, rows=rows, names=self.ledger.names,
                             interval=self.cfg.dispatch_interval)
        imb = tel.per_interval().imbalance()
        if not len(imb):
            return 1.0
        w = max(self.cfg.rebalance_window, 1)
        return float(imb[-w:].mean())

    def maybe_rebalance(self):
        """Host-side control-plane check, run automatically at every dispatch
        boundary when ``cfg.rebalance_threshold > 0``: if the windowed
        load-imbalance factor exceeds the threshold, ask the configured
        rebalance policy for a live->live migration plan and apply it through
        the same cash-conserving ``apply_rebalance`` machinery heals use.
        Returns the recorded :class:`~repro.rebalance.RebalanceEvent`, or
        None (disabled / under threshold / no profitable move)."""
        if self._rebalance is None:
            return None
        trigger = self._windowed_imbalance()
        if trigger <= self.cfg.rebalance_threshold:
            return None
        from repro.ordering import ORD_URL0
        from repro.core import partitioner as PT
        from repro.rebalance import RebalanceEvent
        state = self.state
        row_depth = np.asarray(state.f_valid).sum(axis=1).astype(np.float64)
        os_ = np.asarray(state.order_state, np.float64)
        row_cash = os_[:, 0] + os_[:, ORD_URL0:].sum(axis=1)
        dm = PT.DomainMap(state.slot_of_domain, state.slot_domain,
                          state.shard_alive)
        decision = self._rebalance.plan(self.cfg, dm, row_depth, row_cash)
        if decision is None:
            return None
        with self.tracer.span("rebalance", "rebalance", t=self._t,
                              n_moves=len(decision.moves)):
            self.state = CR.apply_rebalance(state, self.cfg,
                                            decision.new_map)
            jax.block_until_ready(self.state)
        event = RebalanceEvent(step=self._t, trigger=trigger,
                               moves=decision.moves,
                               imbalance_before=decision.imbalance_before,
                               imbalance_after=decision.imbalance_after)
        self.rebalance_events.append(event)
        self.tracer.instant("rebalance", "rebalance", **event.asdict())
        return event

    # -- persistence (train/checkpoint.py) ----------------------------------

    def checkpoint(self, ckpt_dir: str, *, keep: int = 3) -> str:
        """Write the full crawl state atomically; returns the path. With
        telemetry on, the ledger time-series checkpoints alongside (an
        ``obs/`` subdir) so a restore continues it instead of forgetting."""
        from repro.train import checkpoint as ckpt
        if not self.telemetry:
            return ckpt.save(ckpt_dir, self._t, self.state, keep=keep)
        with self.tracer.span("checkpoint", "io", step=self._t):
            path = ckpt.save(ckpt_dir, self._t, self.state, keep=keep)
            steps, rows = self.ledger.arrays()
            ckpt.save(os.path.join(ckpt_dir, _OBS_DIR), self._t,
                      {"steps": steps, "rows": rows}, keep=keep)
        return path

    def restore(self, ckpt_dir: str, *, step: Optional[int] = None
                ) -> "CrawlSession":
        """Restore state (latest step by default) and resync the counter."""
        from repro.train import checkpoint as ckpt
        if not self.telemetry:
            self.state = ckpt.restore(ckpt_dir, self.state, step=step)
            self._t = int(np.asarray(self.state.step))
            return self
        with self.tracer.span("restore", "io"):
            self.state = ckpt.restore(ckpt_dir, self.state, step=step)
            self._t = int(np.asarray(self.state.step))
            # ledger shapes come from the file — any-length target works
            target = {"steps": np.zeros((0,), np.int64),
                      "rows": np.zeros(
                          (0, self.n_shards, len(self.ledger.names)),
                          np.float32)}
            try:
                led = ckpt.restore(os.path.join(ckpt_dir, _OBS_DIR), target,
                                   step=self._t)
                self.ledger.load(np.asarray(led["steps"]),
                                 np.asarray(led["rows"]))
            except FileNotFoundError:
                self.ledger.clear()    # pre-telemetry checkpoint: start fresh
        return self


def chunk_program(cfg: CrawlConfig, mesh, *, axes=("data",),
                  telemetry: bool = False, **kw):
    """One jitted shard_map whose body scans the whole interval. With
    telemetry on, each scanned step also emits its ledger row — an extra
    stacked ``(iv, 1, n_metrics)`` output per shard (global
    ``(iv, n_shards, n_metrics)``), never a host callback. The snapshot
    only READS state, so the crawl trajectory is bit-identical either
    way (tests/test_obs.py pins it)."""
    axes = axes if isinstance(axes, tuple) else (axes,)
    n_shards = int(math.prod(mesh.shape[a] for a in axes))
    local = CR.make_crawl_step(cfg, n_shards=n_shards, axes=axes, **kw)
    specs = state_specs(axes)
    # stacked reports grow a leading (unsharded) time axis
    rep_specs = FetchReport(P(None, axes), P(None, axes))
    iv = cfg.dispatch_interval

    if telemetry:
        from repro.obs import ledger as OL

        def chunk_local(state):
            def body(st, _):
                st, rep = local(st, dispatch=False)
                return st, (rep, OL.snapshot_local(cfg, axes, st,
                                                   dispatch=False))
            state, (reps, rows) = lax.scan(body, state, None,
                                           length=iv - 1)
            state, rep_d = local(state, dispatch=True)
            reps = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b[None]], 0),
                reps, rep_d)
            rows = jnp.concatenate(
                [rows, OL.snapshot_local(cfg, axes, state,
                                         dispatch=True)[None]], 0)
            return state, reps, rows

        return jax.jit(shard_map(chunk_local, mesh=mesh,
                                 in_specs=(specs,),
                                 out_specs=(specs, rep_specs,
                                            P(None, axes))))

    def chunk_local(state):
        state, reps = lax.scan(lambda st, _: local(st, dispatch=False),
                               state, None, length=iv - 1)
        state, rep_d = local(state, dispatch=True)
        reps = jax.tree.map(lambda a, b: jnp.concatenate([a, b[None]], 0),
                            reps, rep_d)
        return state, reps

    return jax.jit(shard_map(chunk_local, mesh=mesh,
                             in_specs=(specs,),
                             out_specs=(specs, rep_specs)))


def chunk_memory(cfg: CrawlConfig, mesh, *, axes=("data",)):
    """``memory_analysis()`` of the compiled fused chunk for ``cfg`` on
    ``mesh`` (per device), from shapes alone: nothing is allocated, so it
    can size a configuration that would not fit."""
    axes = axes if isinstance(axes, tuple) else (axes,)
    n_shards = int(math.prod(mesh.shape[a] for a in axes))
    shapes = jax.eval_shape(lambda: init_state(cfg, n_shards))
    args = jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                          sharding=NamedSharding(mesh, p)),
        shapes, state_specs(axes))
    return chunk_program(cfg, mesh, axes=axes).lower(args).compile(
        ).memory_analysis()
