"""Bounded-staleness async round subsystem: waves, commits, stragglers.

``FLRoundEngine.run_round`` is a synchronous barrier -- the slowest
mediator gates every synchronization round, which is exactly the
heterogeneous-edge pathology the paper discusses (§VII). This module wraps
the engine so mediator groups complete in **waves** and the server overlaps
aggregation with the stragglers' training under a bounded staleness ``S``.

Simulation model (everything deterministic, no wall-clock in the math):

* A ``StragglerModel`` (``core/staleness.py``) assigns seeded slowdown
  factors at one of two granularities: per mediator *slot* (historical;
  duration = ``factor * active_client_slots * E_m``) or per *client*
  (``StragglerSpec(level="client")``; a mediator trains its members
  sequentially, so duration = ``E_m * sum(factor_c)`` over the group --
  a slow device drags whichever mediator Alg. 3 packs it into).
* ``scheduling.partition_waves`` sorts mediators by duration and chunks
  them into waves of ``wave_size`` -- slow mediators/clients are
  co-scheduled into the late waves so the fast waves are never blocked.
* All waves of round ``r`` are dispatched at the round's virtual start
  ``T_r`` from the same params snapshot, and complete at
  ``T_r + max(duration in wave)``.
* The server performs **one commit per round** at virtual time
  ``C_r = max(completion of every wave that is >= S rounds old,
  completion of round r's fastest wave)`` and folds every wave that has
  landed by then. ``T_{r+1} = C_r``: the next round dispatches from the
  committed weights while older stragglers may still be in flight. A wave
  dispatched in round ``q`` therefore folds with staleness
  ``s = r - q <= S`` -- the bound is enforced by construction, because a
  commit always waits for waves that would otherwise exceed it.
* ``S`` is either the fixed ``staleness_bound`` knob or, with
  ``AsyncSpec.adaptive`` set, derived per round from the *observed*
  commit-lag distribution: an ``AdaptiveStaleness`` EWMA over per-wave
  lags (in rounds, on this virtual clock), clamped to ``[s_min, s_max]``.
  A constant lag stream keeps the EWMA bitwise fixed, so the adaptive
  trajectory reproduces the fixed-S one exactly (property-tested).

Staleness-discounted aggregation (the Eq. 6 generalization; discount
policies in ``core/staleness.py``)::

    w~_m        = lambda(s_m) * n_m,      s_m = r - q_m
    params_{r+1} = params_r + sum_m (w~_m / sum_m' w~_m') * delta_m^(q_m)

where ``delta_m^(q)`` is mediator ``m``'s weight delta computed from the
round-``q`` dispatch snapshot, ``n_m`` its sample count, and ``lambda`` is
``constant`` (1), ``polynomial`` ((1+s)^-alpha) or ``exponential``
(e^(-alpha s)). The FedAvg (``aggregate="weights"``) path replaces
``params_r + sum ... delta`` with the discounted weighted average of the
returned weights. Every policy returns exactly 1.0 at ``s = 0``.

``S = 0`` **reproduces the synchronous engine bitwise**: the commit must
wait for every wave of its own round, so all contributions fold together
with ``lambda = 1``; the fold reassembles the full padded-M stack in
schedule order (real mediators first, dummy rows last) and applies the
same jitted Eq. 6 + fold tail the sync round uses (``engine._fold``).
This is asserted, on 1 and 4 forced host devices, across all three
dispatchable stores, in ``tests/test_async_engine.py`` and
``tests/test_async_overlap.py``.

Dispatch modes (``AsyncSpec.dispatch``; the pipeline contract is
documented in ``src/repro/core/README.md``):

* ``"masked"`` (historical default): every wave executes the engine's one
  full padded-M ``wave_fn`` with non-member slot rows zeroed (exact
  no-ops, like dummy mediators) -- one trace serves every wave of every
  reschedule, but a W-wave round costs W x the sync round's row compute
  and the host may sit between waves. ``block_each_wave=True`` adds an
  explicit host block after each wave: the *blocking baseline* the wall
  -clock benchmarks compare against.
* ``"overlapped"``: each wave runs a **sliced** executable
  (``engine.wave_fn_for(width)``) over just its own schedule rows padded
  to the mediator mesh size -- a W-wave round costs ~1x the sync row
  compute -- and the host never blocks between waves or commits: wave
  k+1's mediators are enqueued (and, with JAX async dispatch, training)
  while wave k's contributions and the round's commit are still in
  flight. Commits become a pipelined fold; the only host sync points are
  ``synchronize()`` at eval/checkpoint boundaries and ``flush()``.
  ``overlap_frac`` reports how often a dispatch found the previous
  wave's result still in flight (``jax.Array.is_ready`` probe). The
  commit donates its input state buffer (when the engine donates), which
  is safe exactly because every consumer of snapshot ``r`` is enqueued
  before commit ``r``. Row-permuting stores (``sharded``) route gathers
  by row position and cannot be sliced: overlapped mode keeps the
  pipelined commits but falls back to masked execution per wave.

  Bitwise note: sliced waves feed each row through a batch-width-
  dependent program under the default ``row_exec="vmap"``; the S=0
  bitwise-vs-sync guarantee for overlapped dispatch therefore requires
  ``row_exec="map"`` (the batch-size-invariant row program). Masked
  dispatch preserves the historical guarantee under every config.

Multi-process execution: pass a ``launch/mesh.py::ProcessWaveDispatcher``
to shard waves across ``jax.distributed`` processes -- each process
executes the waves it owns (round-robin) on its process-local mesh and
exchanges the wave payloads host-side through the coordination-service
KV store (cross-process XLA collectives are not available on the CPU
backend). Every process performs every commit, so server states stay
bitwise identical, and every process books the full comm charges, so the
WAN ledger is process-count-invariant (asserted by
``benchmarks/distributed_smoke.py``).

Online augmentation: a wave runs the engine's row program, so the
in-round resample (``core/augmentation.online_draw``) and compacted warp
(``compact_warp``) ride along unchanged; the rows outside a wave are
masked out, so their slots are not warped.  The augmentation keys fork off the engine's round-indexed
``_round_keys`` stream per mediator row -- never off wave membership -- so
a mediator draws the same augmentations whichever wave executes it, and
S=0 stays bitwise-identical to the synchronous engine with augmentation
enabled (``num_round_traces`` stays 1 across waves too; asserted in
tests/test_online_aug.py).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import scheduling
from repro.core.engine import FLRoundEngine
from repro.core.fl import evaluate
from repro.core.staleness import (AdaptiveStaleness, AdaptiveStalenessSpec,
                                  StragglerModel, StragglerSpec,
                                  make_staleness_policy)

PyTree = Any

DISPATCH_MODES = ("masked", "overlapped")


@dataclass(frozen=True)
class AsyncSpec:
    """Async round configuration surfaced through both trainers.

    ``staleness_bound`` is the fixed ``S`` (ignored when ``adaptive`` is
    set); ``wave_size`` is mediators per wave (``0`` = single wave, i.e.
    the synchronous barrier); ``straggler`` drives the simulated fleet
    (mediator- or client-level); ``policy``/``policy_alpha`` pick the
    staleness discount ``lambda``. ``dispatch`` selects masked full-M or
    overlapped sliced execution (module docstring); ``block_each_wave``
    turns the masked loop into the blocking wall-clock baseline (host
    blocks on every wave's result) and is incompatible with overlapped
    dispatch. ``adaptive`` switches ``S`` to the EWMA commit-lag
    controller (``core/staleness.py::AdaptiveStaleness``).
    """
    staleness_bound: int = 0
    wave_size: int = 0
    straggler: StragglerSpec = field(default_factory=StragglerSpec)
    policy: str = "polynomial"
    policy_alpha: float = 0.5
    dispatch: str = "masked"
    block_each_wave: bool = False
    adaptive: AdaptiveStalenessSpec | None = None

    def __post_init__(self):
        if self.staleness_bound < 0:
            raise ValueError("staleness_bound must be >= 0")
        if self.dispatch not in DISPATCH_MODES:
            raise ValueError(f"unknown dispatch mode {self.dispatch!r}; "
                             f"expected one of {DISPATCH_MODES}")
        if self.block_each_wave and self.dispatch == "overlapped":
            raise ValueError("block_each_wave is the blocking baseline; it "
                             "contradicts overlapped dispatch")
        make_staleness_policy(self.policy, self.policy_alpha)  # validates


@dataclass
class _PendingWave:
    """One executed-but-uncommitted wave's contribution."""
    round: int
    wave: int
    t_done: float
    rows: np.ndarray            # schedule indices, sorted ascending
    values: PyTree              # (n_rows, ...) stacked deltas / weights
    weights: jax.Array          # (n_rows,) Eq. 6 sample counts


class AsyncRoundEngine:
    """Bounded-staleness wave executor wrapping an ``FLRoundEngine``.

    The wrapped engine keeps owning params, store, schedule and comm
    meter; this class owns the virtual clock, the wave buffer, the
    dispatch pipeline, and the staleness-discounted commits (see module
    docstring).
    """

    def __init__(self, engine: FLRoundEngine, spec: AsyncSpec, *,
                 dispatcher=None):
        self.engine, self.spec = engine, spec
        self.policy = make_staleness_policy(spec.policy, spec.policy_alpha)
        self._parallel_clients = engine.cfg.aggregate == "weights"
        # dispatch resolution: overlapped mode pipelines the host loop
        # always, and slices wave executables when the store's rows are
        # position-independent (sharded routes gathers by row position,
        # so it keeps masked per-wave execution under the pipeline)
        self._pipelined = spec.dispatch == "overlapped"
        self._sliced = self._pipelined and not engine.store.permutes_rows
        self._dispatcher = dispatcher

        # the commit MUST be jitted: compiled as one program it is
        # bitwise-identical to the aggregation tail inside the engine's
        # round executable, while eager op-by-op dispatch rounds
        # differently on some inputs (jit caches one executable per
        # distinct commit size -- S=0 always commits the full padded M).
        # On a 2-D (mediator, model) mesh the commit mirrors the engine's
        # §8 cycle: gather the model-sharded params, fold the replicated
        # wave stack, reshard on the way out -- exact-byte moves, so the
        # 2-D async trajectory stays bitwise too.
        # Under LoRA the committed state is the replicated adapter dict and
        # the fold is sharding-free; engine._fold is the ONE fold tail
        # shared with the sync round, which is what keeps S=0 bitwise.
        def _commit(state, stacked, weights):
            agg = self.engine._aggregate(stacked, weights)
            return self.engine._fold(state, agg)

        # pipelined commits donate the input state like the sync round
        # does: every consumer of snapshot r (round r's waves) is enqueued
        # before commit r, so the donation can never invalidate an
        # in-flight read. Masked mode keeps the historical no-donation
        # commit (callers may hold pre-commit state references).
        donate = (0,) if (self._pipelined and engine.cfg.donate_params) \
            else ()
        self._commit_fn = jax.jit(_commit, donate_argnums=donate)
        self._straggler: StragglerModel | None = None
        self._adaptive = AdaptiveStaleness(spec.adaptive) \
            if spec.adaptive is not None else None
        self._pending: list[_PendingWave] = []
        self._dummy: tuple | None = None    # current round's dummy-row tail
        self._plan_cache: tuple | None = None   # (plan_args id, host copies)
        self.virtual_time = 0.0             # async clock (commit times)
        self.sync_time = 0.0                # barrier baseline on same fleet
        self.num_commits = 0
        self.commit_log: list[dict] = []
        self.last_wave_stats: dict | None = None
        self.history: list[dict] = []
        # dispatch-pipeline observability (never enters the math):
        # a dispatch counts as overlapped when the previously dispatched
        # wave's result was still in flight at dispatch time
        self.num_dispatches = 0
        self.num_overlapped_dispatches = 0
        self._overlap_checks = 0
        self._last_probe: jax.Array | None = None
        self.wall_commit_wait_s = 0.0       # host time spent in synchronize()
        self.num_syncs = 0
        self._round = 0

    # ---- trainer-facing surface, delegated to the wrapped engine ----
    @property
    def params(self):
        return self.engine.params

    @params.setter
    def params(self, value):
        self.engine.params = value

    @property
    def comm(self):
        return self.engine.comm

    @property
    def telemetry(self):
        """The wrapped engine's observability handle (obs/): one handle
        per engine, shared by both the sync and async drivers."""
        return self.engine.telemetry

    @property
    def sim_speedup(self) -> float:
        """Simulated round-time reduction vs the synchronous barrier.
        Exactly 1.0 before any round has committed (both clocks sit at
        zero; the historical 0/eps division reported a nonsense 0x)."""
        if self.num_commits == 0:
            return 1.0
        return self.sync_time / max(self.virtual_time, 1e-12)

    @property
    def staleness_bound(self) -> int:
        """The bound governing the next commit: the adaptive controller's
        clamped EWMA bound when configured, else the fixed spec knob."""
        if self._adaptive is not None:
            return self._adaptive.bound
        return self.spec.staleness_bound

    @property
    def overlap_frac(self) -> float:
        """Fraction of wave dispatches issued while the previous wave's
        result was still in flight (``is_ready`` probe at dispatch time).
        0.0 under the blocking baseline by construction."""
        if self._overlap_checks == 0:
            return 0.0
        return self.num_overlapped_dispatches / self._overlap_checks

    # ------------------------------------------------------------------
    # one virtual synchronization round: dispatch waves, commit
    # ------------------------------------------------------------------
    def run_round(self) -> None:
        spec, eng = self.spec, self.engine
        tel = eng.telemetry
        wan0 = eng.comm.total_bytes
        round_span = tel.span("round", round=self._round, mode="async",
                              dispatch=spec.dispatch,
                              staleness_bound=self.staleness_bound,
                              wave_size=spec.wave_size,
                              policy=eng.cfg.store)
        with round_span as rsp:
            self._run_round_body(spec, eng, tel)
            rsp.set(wan_bytes=eng.comm.total_bytes - wan0,
                    traces=eng.num_round_traces)
        tel.observe_async_round(self, duration_s=rsp.duration_s)

    def _durations(self, eng, spec, slot_np, row_of, m_real) -> np.ndarray:
        if self._straggler is None:
            # sized to the REAL population (mediator level: Alg. 3 and the
            # random schedule both emit a stable ceil(c/gamma) groups;
            # client level: the whole federation), so the configured
            # straggler fraction is never diluted by dummy padding slots
            self._straggler = StragglerModel(
                spec.straggler, m_real,
                num_clients=eng.data.num_clients
                if spec.straggler.level == "client" else None)
        em = max(1, eng.cfg.mediator_epochs)
        if spec.straggler.level == "client":
            return self._straggler.durations_for_groups(eng.last_groups, em)
        work = slot_np[row_of].sum(axis=1) * em             # (m_real,)
        return self._straggler.durations(work)

    def _run_round_body(self, spec, eng, tel) -> None:
        data_args, plan_args, unperm, slot, row_to_group, m_real = \
            eng.ensure_schedule()
        slot_np = np.asarray(slot)
        m_pad = slot_np.shape[0]
        rtg = np.asarray(row_to_group)
        row_of = np.zeros(m_real, np.int64)
        for rr, g in enumerate(rtg):
            if g >= 0:
                row_of[g] = rr
        durations = self._durations(eng, spec, slot_np, row_of, m_real)
        waves, wstats = scheduling.partition_waves(durations, spec.wave_size)
        self.last_wave_stats = wstats

        r = self._round
        t0 = self.virtual_time
        keys = eng._round_keys(rtg, m_real, round_idx=r)
        if self._sliced:
            # host copies of the schedule tensors the slices are cut from
            # (plan reuses the cache until the engine repacks; keys are
            # per-round). Tiny arrays -- (M_pad, gamma) ints.
            if self._plan_cache is None or self._plan_cache[0] is not plan_args:
                self._plan_cache = (plan_args,
                                    tuple(np.asarray(a) for a in plan_args))
            plan_np = self._plan_cache[1]
            keys_np = np.asarray(keys)
        snapshot = eng.server_state         # dispatch snapshot for round r
        for wi, wave in enumerate(waves):
            rows = np.sort(np.asarray(wave, np.int64))
            wave_span = tel.span("wave", wave=wi, round=r,
                                 mediators=int(rows.size),
                                 sim_done=float(t0 + wstats["wave_times"][wi]))
            with wave_span as wsp:
                overlapped_now = self._probe_overlap()
                owner_here = self._dispatcher is None or \
                    self._dispatcher.owner_of(r, wi) == \
                    self._dispatcher.process_index
                need_dummy = wi == 0
                if owner_here:
                    with tel.span("dispatch_gap", wave=wi, round=r,
                                  overlapped=overlapped_now):
                        if self._sliced:
                            vals, wts, dummy = self._dispatch_sliced(
                                eng, snapshot, data_args, plan_np, slot_np,
                                keys_np, row_of[rows], m_real, m_pad,
                                need_dummy)
                        else:
                            vals, wts, dummy = self._dispatch_masked(
                                eng, snapshot, data_args, plan_args, unperm,
                                slot, keys, rows, row_of, m_real, m_pad,
                                need_dummy)
                    if self._dispatcher is not None:
                        self._publish_wave(r, wi, vals, wts, dummy)
                else:
                    vals, wts, dummy = self._receive_wave(r, wi, need_dummy)
                if need_dummy:
                    self._dummy = dummy
                self._last_probe = wts
                if spec.block_each_wave:
                    # the blocking baseline: the host waits for every
                    # wave's result before dispatching the next
                    jax.block_until_ready((vals, wts))
                if not self._pipelined:
                    wsp.sync_on((vals, wts))
                clients = int(slot_np[row_of[rows]].sum())
                wave_wan0 = eng.comm.total_bytes
                # comm charges are schedule-derived and booked on EVERY
                # process of a multi-process run -- the WAN ledger is
                # dispatch-mode- and process-count-invariant
                if self._parallel_clients:
                    eng.comm.fedavg_wave(clients)
                else:
                    eng.comm.astraea_wave(clients, len(rows),
                                          eng.cfg.mediator_epochs)
                if eng._model_size > 1 and not eng._tp_rows:
                    # every gather-oracle wave execution gathers the
                    # model-sharded weights (wave_fn's _prep: the params
                    # snapshot, or the LoRA backbone operand) -- one
                    # intra-pod charge per wave, unlike the WAN ledger
                    # where waves only re-partition a round's fixed total.
                    # TP-rows waves never gather.
                    eng.comm.model_axis_round(eng._msize * eng._model_size,
                                              eng._model_size)
                if eng.store.exchange_bytes_per_round:
                    # masked waves run the full padded-M program, so the
                    # sharded serve exchange rides the interconnect per
                    # wave (sliced waves only exist for exchange-free
                    # stores: exchange_bytes_per_round == 0 there)
                    eng.comm.store_exchange(
                        eng.store.exchange_bytes_per_round)
                self._pending.append(_PendingWave(
                    r, wi, t0 + wstats["wave_times"][wi], rows, vals, wts))
                wsp.set(clients=clients,
                        wan_bytes=eng.comm.total_bytes - wave_wan0)
        eng.comm.end_round()

        # ---- commit C_r: wait for staleness-expired waves + the round's
        # fastest wave, fold everything that has landed by then ----
        s_bound = self.staleness_bound
        due = [p.t_done for p in self._pending if p.round <= r - s_bound]
        c_time = max(due + [t0 + wstats["wave_times"][0]])
        ready = [p for p in self._pending if p.t_done <= c_time]
        self._pending = [p for p in self._pending if p.t_done > c_time]
        if self._adaptive is not None:
            # feed the controller the lags this commit realized: folded
            # waves lag r - q rounds; still-pending waves will lag at
            # least one more. Virtual-clock quantities only.
            for p in ready:
                self._adaptive.observe(r - p.round)
            for p in self._pending:
                self._adaptive.observe(r - p.round + 1)
        self._fold(ready, r, c_time)
        self.virtual_time = c_time
        self.sync_time += wstats["barrier_time"]
        self._round += 1
        eng._round = self._round

    # ------------------------------------------------------------------
    # wave execution paths
    # ------------------------------------------------------------------
    def _probe_overlap(self) -> bool:
        """Non-blocking check whether the previously dispatched wave is
        still in flight (observability only -- never gates dispatch)."""
        self.num_dispatches += 1
        if self._last_probe is None:
            return False
        self._overlap_checks += 1
        try:
            in_flight = not self._last_probe.is_ready()
        except AttributeError:          # non-jax probe (received wave)
            in_flight = False
        if in_flight:
            self.num_overlapped_dispatches += 1
        return in_flight

    def _dispatch_masked(self, eng, snapshot, data_args, plan_args, unperm,
                         slot, keys, rows, row_of, m_real, m_pad, need_dummy):
        """Historical execution: the full padded-M ``wave_fn`` with
        non-member slot rows zeroed (exact no-ops)."""
        mask = np.zeros((m_pad, 1), np.float32)
        mask[row_of[rows]] = 1.0
        wslot = slot * jnp.asarray(mask)    # members bitwise, rest 0
        stacked, weights = eng.wave_fn(snapshot, data_args, plan_args,
                                       unperm, wslot, keys,
                                       *eng.extra_args())
        rj = jnp.asarray(rows)
        vals = jax.tree.map(lambda a: a[rj], stacked)
        wts = weights[rj]
        dummy = None
        if need_dummy:
            # dummy-row tail (weight exactly 0) completing the padded
            # stack so an S=0 commit aggregates the byte-identical input
            # of the synchronous round executable
            dj = jnp.arange(m_real, m_pad)
            dummy = (jax.tree.map(lambda a: a[dj], stacked), weights[dj])
        return vals, wts, dummy

    def _dispatch_sliced(self, eng, snapshot, data_args, plan_np, slot_np,
                         keys_np, pos, m_real, m_pad, need_dummy):
        """Overlapped execution: ``wave_fn_for(width)`` over just this
        wave's schedule rows, padded to the mediator mesh size with no-op
        rows (zero plan/slot/keys -- the exact bytes of the schedule's
        dummy rows, so padding outputs ARE dummy-row outputs).

        The round's dummy tail is rebuilt by broadcasting one no-op row's
        output: under ``row_exec="map"`` every no-op row of every width
        produces identical bits, so the commit stack matches the sync
        round's byte for byte (dummy weights are exactly 0 besides).
        """
        n = int(pos.size)
        msize = eng._msize
        width = -(-n // msize) * msize
        n_dummy = m_pad - m_real
        if need_dummy and n_dummy > 0 and width == n:
            width += msize      # guarantee a no-op row to clone the tail from

        def pad_rows(a_np):
            out = np.zeros((width,) + a_np.shape[1:], a_np.dtype)
            out[:n] = a_np[pos]
            return jnp.asarray(out)

        plan_w = tuple(pad_rows(a) for a in plan_np)
        slot_w = pad_rows(slot_np)
        keys_w = pad_rows(keys_np)
        unperm_w = jnp.arange(width, dtype=jnp.int32)
        stacked, weights = eng.wave_fn_for(width)(
            snapshot, data_args, plan_w, unperm_w, slot_w, keys_w,
            *eng.extra_args())
        vals = jax.tree.map(lambda a: a[:n], stacked)
        wts = weights[:n]
        dummy = None
        if need_dummy:
            dummy = (jax.tree.map(
                lambda a: jnp.broadcast_to(a[n], (n_dummy,) + a.shape[1:]),
                stacked), jnp.broadcast_to(weights[n], (n_dummy,))) \
                if n_dummy > 0 else \
                (jax.tree.map(lambda a: a[:0], stacked), weights[:0])
        return vals, wts, dummy

    # ------------------------------------------------------------------
    # multi-process wave exchange (launch/mesh.py::ProcessWaveDispatcher)
    # ------------------------------------------------------------------
    def _payload_treedef(self):
        return jax.tree.structure(self.engine.server_state)

    def _publish_wave(self, r, wi, vals, wts, dummy) -> None:
        """Ship an owned wave's contribution to the other processes
        (host-side KV exchange; forces materialization, which is the
        per-wave sync a multi-process run accepts in return for
        process-level parallelism)."""
        leaves = [np.asarray(x) for x in jax.tree.leaves(vals)]
        leaves.append(np.asarray(wts))
        if dummy is not None:
            leaves.extend(np.asarray(x) for x in jax.tree.leaves(dummy[0]))
            leaves.append(np.asarray(dummy[1]))
        self._dispatcher.publish(f"wave-{r}-{wi}", leaves)

    def _receive_wave(self, r, wi, expect_dummy):
        leaves = self._dispatcher.receive(f"wave-{r}-{wi}")
        tdef = self._payload_treedef()
        nv = tdef.num_leaves
        vals = jax.tree.unflatten(tdef,
                                  [jnp.asarray(a) for a in leaves[:nv]])
        wts = jnp.asarray(leaves[nv])
        dummy = None
        if expect_dummy:
            dvals = jax.tree.unflatten(
                tdef, [jnp.asarray(a) for a in leaves[nv + 1:2 * nv + 1]])
            dummy = (dvals, jnp.asarray(leaves[2 * nv + 1]))
        return vals, wts, dummy

    # ------------------------------------------------------------------
    # commits
    # ------------------------------------------------------------------
    def _fold(self, ready: list[_PendingWave], r: int, c_time: float) -> None:
        """One server commit: staleness-discounted Eq. 6 over ``ready``."""
        assert ready, "a commit always folds at least the round's fast wave"
        with self.telemetry.span("commit", round=r,
                                 sim_time=float(c_time)) as csp:
            self._fold_traced(ready, r, c_time, csp)

    def _fold_traced(self, ready, r, c_time, csp) -> None:
        parts_v, parts_w, stales = [], [], []
        for q in sorted({p.round for p in ready}):
            ws = [p for p in ready if p.round == q]
            rows = np.concatenate([p.rows for p in ws])
            order = jnp.asarray(np.argsort(rows, kind="stable"))
            vals = jax.tree.map(lambda *xs: jnp.concatenate(xs)[order],
                                *[p.values for p in ws])
            wts = jnp.concatenate([p.weights for p in ws])[order]
            s = r - q
            if s > 0:       # s == 0 keeps the weights bitwise untouched
                wts = wts * jnp.float32(self.policy(s))
            parts_v.append(vals)
            parts_w.append(wts)
            stales.extend([s] * rows.size)
        dvals, dwts = self._dummy
        stack = jax.tree.map(lambda *xs: jnp.concatenate(xs),
                             *(parts_v + [dvals]))
        wvec = jnp.concatenate(parts_w + [dwts])
        if self.engine._model_size > 1 and self.engine._lora_mapping is None:
            # the jitted commit gathers the model-sharded params too; the
            # LoRA commit folds replicated adapters (no gather)
            self.engine.comm.model_axis_round(
                self.engine._msize * self.engine._model_size,
                self.engine._model_size)
        self.engine.server_state = self._commit_fn(self.engine.server_state,
                                                   stack, wvec)
        self.num_commits += 1
        self.commit_log.append({
            "round": r, "time": float(c_time),
            "folded_rows": int(sum(p.rows.size for p in ready)),
            "staleness": stales,
            "staleness_bound": self.staleness_bound,
            "pending_after": len(self._pending),
        })
        csp.set(folded_rows=self.commit_log[-1]["folded_rows"],
                staleness_max=max(stales) if stales else 0,
                pending_after=len(self._pending))
        if not self._pipelined:
            csp.sync_on(self.engine.server_state)

    def synchronize(self) -> float:
        """Drain the dispatch pipeline: block until the latest commit (and
        transitively every wave feeding it) has landed on device.

        The ONLY host sync point of overlapped dispatch -- ``fit`` calls
        it at eval boundaries and ``flush`` at the end of training.
        Returns the wall seconds spent waiting; purely observability
        (``commit_lag`` span + ``wall_commit_wait_s``), never part of the
        virtual-clock math."""
        t0 = time.perf_counter()
        with self.telemetry.span("commit_lag", round=self._round,
                                 pending=len(self._pending)) as sp:
            jax.block_until_ready(self.engine.server_state)
            waited = time.perf_counter() - t0
            sp.set(waited_s=waited)
        self.wall_commit_wait_s += waited
        self.num_syncs += 1
        return waited

    def flush(self) -> None:
        """Fold every still-pending straggler wave (end of training).

        Pending waves are at most ``S`` rounds behind by construction, so
        the final fold discounts them by ``s = r_final - q <= S``. A
        no-op (not an error) when nothing is pending -- including before
        any round has run.
        """
        if not self._pending:
            if self.num_commits:
                self.synchronize()
            return
        c_time = max(p.t_done for p in self._pending)
        ready, self._pending = self._pending, []
        self._fold(ready, self._round, c_time)
        self.virtual_time = max(self.virtual_time, c_time)
        self.synchronize()
        # the flush commit lands after the last round's absorption: emit
        # one final post-flush metrics snapshot so its staleness
        # observations reach the registry too
        self.telemetry.observe_async_round(self)

    # ------------------------------------------------------------------
    # driving
    # ------------------------------------------------------------------
    def fit(self, rounds: int, eval_every: int = 10) -> list[dict]:
        eng = self.engine
        for i in range(rounds):
            last = i == rounds - 1      # robust to repeated fit() calls
            self.run_round()
            if last:
                self.flush()
            if self._round % eval_every == 0 or last:
                self.synchronize()      # eval is a pipeline sync point
                m = evaluate(eng.model, eng.merged_params(),
                             eng.data.test_images, eng.data.test_labels)
                stales = [s for c in self.commit_log for s in c["staleness"]]
                m.update(round=self._round, traffic_mb=eng.comm.megabytes,
                         sim_time=self.virtual_time,
                         sync_sim_time=self.sync_time,
                         sim_speedup=self.sim_speedup,
                         commits=self.num_commits,
                         overlap_frac=self.overlap_frac,
                         staleness_bound=self.staleness_bound,
                         staleness_mean=float(np.mean(stales)) if stales
                         else 0.0,
                         staleness_max=int(max(stales)) if stales else 0)
                if eng.last_schedule_stats and \
                        "kld_mean" in eng.last_schedule_stats:
                    m["mediator_kld_mean"] = \
                        eng.last_schedule_stats["kld_mean"]
                self.history.append(m)
        return self.history
