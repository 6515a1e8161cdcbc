"""Sharded FL round engine: pack once, gather on device, shard mediators.

One engine drives both algorithms in this repo:

* **Astraea** (paper Alg. 1/3): KLD-greedy mediator schedule, up to ``gamma``
  clients per mediator trained *sequentially* for ``E_m`` mediator epochs,
  FedAvg aggregation (Eq. 6) over the mediator weight *deltas*.
* **FedAvg** (the baseline): exactly the ``gamma=1`` + random-singleton
  schedule + full-weight aggregation configuration of the same engine --
  every "mediator" holds one client training from the global weights.

What makes it an engine rather than a trainer loop:

1. **Pack once, place by policy.** The padded per-client arrays
   ``(K, pad, ...)`` are packed at construction into a ``ClientStore``
   (``core/client_store.py``) under one of four placement policies:

   * ``replicated`` -- the whole store on every device. Fastest gathers;
     per-device bytes = K * slice, so K is bounded by one device's HBM.
   * ``sharded`` -- client axis partitioned over the ``mediator`` mesh
     axis (per-device bytes = K/n * slice). Each mediator's ``x_all[idx]``
     gather is routed at schedule time: locally-owned clients read from
     the device's shard; remote ones ride the serve-slice exchange --
     by default a *ragged* point-to-point ppermute ring that ships each
     slice only to the shards whose rows read it
     (``cfg.store_exchange="ragged"``), or the historical fixed-capacity
     ``all_gather`` of every shard's full serve buffer (``"gather"``);
     both are static-shaped across reschedules and bit-identical.
     Mediator rows are placed by the locality pass
     ``scheduling.place_mediators`` to minimize cross-shard fetches.
   * ``host`` -- the federation stays in host RAM (per-device bytes =
     min(K, c) * slice); the unique scheduled clients are streamed to
     device once per reschedule into a fixed-capacity compact buffer.
   * ``spilled`` -- the streaming contract of ``host`` with the
     federation itself demoted to a disk/mmap tier (or a lazy row
     source) behind a ``min(K, c)``-row RAM cache; when rescheduling
     every round, the engine pre-draws the NEXT round's selection and
     hands it to ``store.prefetch`` so the tier reads overlap the
     current round's device compute (the rng draw order is unchanged,
     so trajectories stay bitwise identical).

   The engine also accepts a *streaming federation* instead of packed
   arrays -- any ``data`` without ``client_images`` but with the row-
   source protocol (``rows(ids)``/``num_clients``/``nbytes_per_client``
   + ``pad``/``num_classes``/``client_counts()``; see
   ``data.synthetic.StreamingFederation``) feeds the host/spilled
   stores directly, so a K=1e6 federation runs rounds on a device (and
   host) footprint fixed by ``clients_per_round``, never by K.

   A schedule is a tiny ``(M, gamma)`` int32 gather index plus a 0/1 slot
   mask. When rescheduling every round, ``run_round`` builds the next
   round's schedule one round ahead: it draws that cohort and enqueues
   its Alg. 3 and Fig. 7 in one device launch before dispatching the
   current round, and reads them once the current round is queued, so the
   read waits on the round before while the chip keeps working. The
   cohort order, the schedules and the trajectory are those of building
   each schedule at its round's start (``num_schedules_ahead`` counts the
   rounds whose schedule was ready). ``run_round`` never rebuilds host
   buffers (the old trainers
   re-packed ``(M, gamma, pad, ...)`` on the host every round). Slot-mask
   zeros make empty client slots exact no-ops (masked loss is 0 => zero
   grads => zero Adam updates), so a dummy slot may harmlessly gather any
   resident row.  Store traffic is metered: host->device streaming and
   the sharded serve exchange land on the CommMeter's intra-pod ledger
   (``store_stream`` / ``store_exchange``); the WAN ledger is invariant
   to placement policy.
2. **Mediator sharding.** Mediators are distributed over the ``mediator``
   axis of a device mesh via shard_map; ``M`` is padded up to the mesh
   size with zero-weight dummy mediators (also exact no-ops). On a 1-device
   CPU mesh this degrades to plain vmap semantics bit-for-bit.
3. **Fixed-M compilation.** ``pad_mediators_to`` fixes the padded mediator
   count across reschedules (the trainers default it to ``ceil(c/gamma)``),
   and every store keeps its plan shapes static, so the round executable
   is traced exactly once per engine no matter how often the KLD schedule
   changes -- ``num_round_traces`` counts traces and is asserted in tests.
4. **Donated params.** The round executable receives the parameter buffer
   with ``donate_argnums`` so the server-side update is in-place on
   accelerators.
5. **Kernel aggregation.** ``use_kernel_agg`` routes Eq. 6 through the
   ``fedavg_agg`` Pallas kernel (interpret-mode on CPU, Mosaic on TPU);
   default is the pure-jnp ``weighted_average`` (same math, XLA-fused).
6. **Wave entry point.** ``wave_fn`` is the same full padded-M program
   stopped just before aggregation, for the bounded-staleness async
   subsystem (``core/async_engine.py``): a wave zeroes the slot rows of
   mediators outside it (exact no-ops, like dummy mediators), so the one
   trace serves every wave of every reschedule and ``num_round_traces``
   stays 1 for an async engine too.
7. **Online rebalancing.** When the engine is built with an ``aug_plan``
   (the server's tiny ``(num_classes,)`` Alg. 2 array, broadcast once and
   fed into the jitted round as a true operand), every row's per-slot
   data is redrawn INSIDE the round program before training
   (``augmentation.online_draw``: a fixed-shape class-conditional resample
   from round-indexed keys), then the masked-in slots drawn as augmented
   are warped in one ``augmentation.compact_warp`` over all the device's
   slots, at the smallest capacity of a fixed ladder that holds them
   (``last_warp_slots`` / ``last_warp_capacity``).  The store
   keeps the *raw* clients (per-device bytes stay at the pre-augmentation
   packed size under every placement policy), Alg. 3 schedules on the
   expected post-augmentation histograms ``counts * (1 + plan)``, and the
   Eq. 6 weights become the expected post-augmentation sizes
   ``sum(mask * (1 + plan[y]))``.  Since the hook lives inside the jitted
   round, augmentation adds zero traces: ``num_round_traces`` stays 1,
   including across async waves (aug keys derive from the per-row round
   keys, never from wave membership).  With ``adaptive_aug_alpha`` set,
   the plan is *recomputed from the selected cohort's label histograms at
   every reschedule* (the class-imbalance-FL "rebalance per round"
   regime): the plan is a round operand, so only its value changes -- the
   one compiled executable is reused -- and each re-broadcast is metered
   on the WAN ledger via ``CommMeter.plan_broadcast``.
8. **2-D mediator x model mesh.** On a ``make_fl_mesh(mediator=n,
   model=t)`` mesh the round shard_map is manual over *both* axes: the
   ``model`` axis carries no variation inside the round body (every model
   column runs the identical full-parameter row program -- see below), so
   making it manual costs nothing and sidesteps the XLA-CPU partitioner
   crash that ``lax.scan`` under a partial-auto shard_map trips (the same
   bug family as the remat note in ``launch/dryrun.py``; the transformer
   round keeps ``model`` compiler-auto for true tensor-parallel compute
   and only ever executes that way on TPU meshes).  The sharding
   contract:

   * **params** are sharded along ``model`` by the logical-axis rule
     tables (``param_shardings(model.param_specs(), mesh,
     model_only_rules())``) and replicated along ``mediator`` -- FL
     replicas diverge during a round, so weights never shard over the
     mediator axis.  At rest (between rounds, and in the optimizer-free
     server state) every device holds ``1/t`` of the model: per-device
     param bytes shrink by the model-axis factor (surfaced through
     ``ClientStore.stats()``).
   * **client batches / schedules / keys** are partitioned on
     ``mediator`` and replicated on ``model`` (``P("mediator")`` never
     mentions ``model``); the sharded store's client axis partitions
     over the mediator submesh rows only.
   * **inside the round** the params are gathered to model-replicated
     (``with_sharding_constraint`` -- one all-gather per round), each
     mediator row then runs the *identical full-parameter row program*
     on every model column, and the updated params are resharded back
     onto the model axis on the way out.  Gather and reshard move exact
     bytes and the row program never sees a sharded contraction, so the
     2-D trajectory is bitwise identical to the 1-D one -- ``model=1``
     reproduces today's 1-D trajectories exactly, and with
     ``row_exec="map"`` a ``2x2`` mesh matches a ``4x1`` mesh bit for
     bit across all three stores, sync and async (asserted in
     tests/test_model_mesh.py).  This is residency (ZeRO-style) model
     sharding: compute is replicated along ``model`` while HBM is not --
     the right trade at CNN scale; true tensor-parallel *compute* rides
     the same mesh through ``launch/steps.py:make_fl_round``, which
     delegates its shard_map and Eq. 6 to this module
     (``mediator_shard_map`` / ``psum_eq6``) so there is one federated
     round implementation.
   * **Eq. 6** reduces over the mediator axis only: the stacked mediator
     outputs are constrained to replicated (an all-gather across
     ``mediator``; the ``model`` columns already agree) and the weighted
     average runs in single-device order.  Model-axis collectives are
     accounted on the separate intra-pod ledger
     (``CommMeter.model_axis_round``), never on the WAN ledger that
     backs the paper's 82% traffic claim.

Bit-identity guarantees: every store feeds identical per-slot values into
identical per-row programs (gathers move exact bits), the sharded store's
locality permutation is undone before aggregation (``unperm``), and the
stacked outputs are constrained to replicated sharding first, so the
Eq. 6 reduction always runs in single-device order. Hence at any FIXED
mesh size the three stores produce bitwise-identical trajectories. Across
*different* mesh sizes, XLA's batched kernels are not bit-stable in the
vmap batch width, so the default ``row_exec="vmap"`` matches only to fp
tolerance; ``row_exec="map"`` runs rows through a batch-size-invariant
program and is bitwise identical across any mesh size and store
combination (asserted in tests/test_client_store.py). RNG note: per-round
keys are split at the *real* mediator count before dummy-row padding
(``jax.random.split`` is not prefix-stable) and follow mediators through
placement, so the trajectory is independent of placement policy, and
bit-identical to the pre-engine trainers on a single device.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, replace as dc_replace
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import augmentation, scheduling
from repro.core.client_store import EXCHANGES, POLICIES, build_client_store
from repro.core.comm import CommMeter
from repro.core.fl import (LocalSpec, evaluate, make_client_update,
                           weighted_average)
from repro.core.mediator import make_mediator_update
from repro.data.federated import FederatedDataset
from repro.launch.mesh import (default_fl_mesh, model_axis_size,
                               replicated_sharding)
from repro.launch.sharding import model_only_rules, param_shardings
from repro.models import lora as lora_lib
from repro.models.cnn import Model, count_params
from repro.obs.telemetry import as_telemetry
from repro.optim.optimizers import Optimizer

PyTree = Any


def _pad_multiple(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


# --------------------------------------------------------------------------
# The shared federated-round building blocks (the ONE round implementation:
# FLRoundEngine composes them below; launch/steps.py:make_fl_round delegates
# its transformer round to the same helpers)
# --------------------------------------------------------------------------

def mediator_shard_map(body, mesh, in_specs, out_specs, *,
                       mediator_axes: tuple = ("mediator",),
                       manual_axes: tuple | None = None):
    """shard_map a per-mediator ``body`` over the mediator axes of ``mesh``.

    ``manual_axes`` defaults to ``mediator_axes``: every other mesh axis
    (the tensor-parallel ``model`` axis) then stays compiler-auto, so
    per-mediator model sharding rides along pjit-style -- the transformer
    round's configuration (``launch/steps.py:make_fl_round``).  The FL
    engine instead passes every mesh axis as manual (its model columns
    run identical programs, and XLA-CPU's partitioner crashes on
    ``lax.scan`` under partial-auto -- see the engine docstring §8).

    The varying-manual-axes checker stays off: under it, differentiating
    the replicated (``P()``) params inside a row would transpose their
    implicit broadcast into a ``psum`` over the mediator axis, summing
    every mediator's gradient instead of keeping each row's own.
    """
    manual = tuple(manual_axes if manual_axes is not None else mediator_axes)
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=set(manual),
                         check_vma=False)


def eq6_aggregate(stacked: PyTree, weights, mesh, *,
                  use_kernel_agg: bool = False) -> PyTree:
    """Eq. 6 over stacked ``(M, ...)`` mediator outputs, in a fixed order.

    Constrains the stack to replicated first (the only cross-device
    collective is an all-gather over ``mediator``; the ``model`` columns
    already hold identical rows), so the weighted-average reduction always
    runs in single-device order -- the bit-stability anchor of the whole
    engine.  ``use_kernel_agg`` routes through the fused ``fedavg_agg``
    Pallas kernel instead of the pure-jnp path (same math).
    """
    rep = replicated_sharding(mesh)
    stacked = jax.lax.with_sharding_constraint(stacked, rep)
    weights = jax.lax.with_sharding_constraint(weights, rep)
    if use_kernel_agg:
        from repro.kernels import ops as kops
        return kops.fedavg_agg_tree(stacked, weights)
    return weighted_average(stacked, weights)


def psum_eq6(delta: PyTree, n_m, mediator_axes: tuple) -> PyTree:
    """Eq. 6 *inside* the manual region: weighted psum over the mediator
    axes.  The production-memory-profile variant -- no ``(M, ...)`` stack
    is ever materialized -- used by the transformer round
    (``launch/steps.py:make_fl_round``) on the big meshes, where the
    replicated stack of ``eq6_aggregate`` would not fit."""
    num = jax.tree.map(lambda d: jax.lax.psum(d * n_m, mediator_axes), delta)
    den = jax.lax.psum(n_m, mediator_axes)
    return jax.tree.map(lambda d: d / den, num)


@dataclass
class _CohortSchedule:
    """One round's schedule on its way to the round program: the cohort,
    the adaptive Alg. 2 plan it implies (``None`` without one), its Alg. 3
    and Fig. 7 launch (``None`` for FedAvg's singletons) and, once read,
    its client groups and Fig. 7 statistics."""
    round: int
    sel: np.ndarray
    plan: np.ndarray | None
    pending: scheduling.PendingSchedule | None
    groups: list[list[int]] | None = None
    stats: dict | None = None


def _per_device_param_bytes(params: PyTree) -> int:
    """Bytes of parameter residency on one device (the first addressable
    one): full leaf bytes when replicated, ``1/model`` when sharded."""
    total = 0
    for leaf in jax.tree.leaves(params):
        shards = getattr(leaf, "addressable_shards", None)
        if not shards:
            total += leaf.nbytes
            continue
        dev = shards[0].device
        total += sum(s.data.nbytes for s in shards if s.device == dev)
    return int(total)


@dataclass(frozen=True)
class EngineConfig:
    """Static round configuration. ``astraea()``/``fedavg()`` build the two
    canonical settings; everything between them is a valid ablation."""
    clients_per_round: int                  # c
    gamma: int                              # max clients per mediator
    local: LocalSpec                        # B, E
    mediator_epochs: int = 1                # E_m
    schedule: str = "kld"                   # "kld" (Alg. 3) | "random"
    aggregate: str = "delta"                # "delta" (Astraea) | "weights" (FedAvg)
    store: str = "replicated"               # client-store placement policy
    # sharded store serve exchange: "ragged" (ppermute ring, exact bytes)
    # or "gather" (historical fixed-capacity all_gather); bit-identical
    store_exchange: str = "ragged"
    # spilled-store streaming pipeline: how many reschedules ahead the
    # engine pre-draws selections and hands them to store.prefetch (the
    # rng draw ORDER is unchanged, so depth never perturbs trajectories),
    # and the host-side LRU row-cache size in rows (None = 2x capacity)
    store_prefetch_depth: int = 1
    store_lru_rows: int | None = None
    # per-device mediator-row execution: "vmap" vectorizes rows (fastest on
    # few devices), "map" runs them serially with a batch-size-invariant
    # program, making trajectories bit-identical across ANY mesh size (XLA
    # batching picks different reduction strategies per batch size, so vmap
    # is only bit-stable at a fixed mesh; see tests/test_client_store.py)
    row_exec: str = "vmap"
    # resampler for the online-augmentation warp (augmentation.warp_batch):
    # "auto" = the fused Pallas kernel on TPU, the map_coordinates
    # reference elsewhere; only consulted when the engine holds an aug plan
    warp_impl: str = "auto"
    use_kernel_agg: bool = False
    # route Alg. 3 rescheduling through the one-launch Pallas greedy pass
    # (kernels.kld_greedy_picks) instead of the XLA masked-argmin scan;
    # identical mediator lists (property-tested), O(1) kernel launches per
    # pass -- the Mosaic path for 1e5+-client reschedules on TPU
    reschedule_kernel: bool = False
    reschedule_every_round: bool = False
    # true tensor-parallel row compute (§8 TP mode): "auto" turns it on
    # when the mesh has a model axis AND the backend's partitioner can
    # handle lax.scan under partial-auto shard_map (TPU/GPU); True forces
    # it (raising on CPU); False keeps the gather->replicated-compute
    # oracle everywhere. model=1 meshes always resolve to the oracle.
    tp_rows: bool | str = "auto"
    # LoRA adapter exchange: rank of the per-tensor adapter mapping table
    # built from model.param_specs() (models/lora.py). None = full-delta
    # exchange (historical behavior); 0 = fully frozen backbone; at
    # rank >= models.lora.full_rank(specs) every entry degenerates to
    # dense and the trajectory is bitwise the full-delta oracle's.
    lora_rank: int | None = None
    lora_alpha: float | None = None         # merge scale; None = rank (1.0)
    donate_params: bool = True
    # floor for the padded mediator count (rounded up to the mesh size);
    # fixes M across reschedules so the round executable is jitted once
    pad_mediators_to: int | None = None
    seed: int = 0

    def __post_init__(self):
        if self.schedule not in ("kld", "random"):
            raise ValueError(f"unknown schedule {self.schedule!r}")
        if self.aggregate not in ("delta", "weights"):
            raise ValueError(f"unknown aggregate {self.aggregate!r}")
        if self.store not in POLICIES:
            raise ValueError(f"unknown client-store policy {self.store!r}; "
                             f"expected one of {POLICIES}")
        if self.store_exchange not in EXCHANGES:
            raise ValueError(f"unknown store_exchange {self.store_exchange!r}; "
                             f"expected one of {EXCHANGES}")
        if self.row_exec not in ("vmap", "map"):
            raise ValueError(f"unknown row_exec {self.row_exec!r}")
        if self.store_prefetch_depth < 1:
            raise ValueError("store_prefetch_depth must be >= 1")
        if self.store_lru_rows is not None and self.store_lru_rows < 0:
            raise ValueError("store_lru_rows must be >= 0")
        if self.warp_impl not in augmentation.WARP_IMPLS:
            raise ValueError(f"unknown warp_impl {self.warp_impl!r}; "
                             f"expected one of {augmentation.WARP_IMPLS}")
        if self.aggregate == "weights" and self.gamma != 1:
            raise ValueError("weight aggregation implies gamma=1 (FedAvg)")
        if self.pad_mediators_to is not None and self.pad_mediators_to < 1:
            raise ValueError("pad_mediators_to must be >= 1")
        if self.tp_rows not in (True, False, "auto"):
            raise ValueError(f"tp_rows must be True, False or 'auto', "
                             f"got {self.tp_rows!r}")
        if self.lora_rank is not None and self.lora_rank < 0:
            raise ValueError("lora_rank must be >= 0")
        if self.lora_alpha is not None and self.lora_rank is None:
            raise ValueError("lora_alpha requires lora_rank")

    @classmethod
    def astraea(cls, *, clients_per_round: int, gamma: int, local: LocalSpec,
                mediator_epochs: int = 1, **kw) -> "EngineConfig":
        return cls(clients_per_round=clients_per_round, gamma=gamma,
                   local=local, mediator_epochs=mediator_epochs,
                   schedule="kld", aggregate="delta", **kw)

    @classmethod
    def fedavg(cls, *, clients_per_round: int, local: LocalSpec,
               **kw) -> "EngineConfig":
        """FedAvg == one client per mediator, fresh random singleton schedule
        every round, full-weight aggregation."""
        kw.setdefault("reschedule_every_round", True)
        return cls(clients_per_round=clients_per_round, gamma=1, local=local,
                   schedule="random", aggregate="weights", **kw)


class FLRoundEngine:
    """Device-resident federated round executor (see module docstring)."""

    def __init__(self, model: Model, opt: Optimizer, data: FederatedDataset,
                 cfg: EngineConfig, *, mesh=None,
                 loss_fn: Callable | None = None,
                 aug_plan: np.ndarray | None = None,
                 adaptive_aug_alpha: float | None = None,
                 telemetry=None):
        self.model, self.opt, self.data, self.cfg = model, opt, data, cfg
        # host-side observability handle (obs/): spans + metrics around --
        # never inside -- the jitted round, so telemetry on/off is bitwise
        # identical and adds zero traces (tests/test_telemetry.py)
        self.telemetry = as_telemetry(telemetry)
        self.mesh = mesh if mesh is not None else default_fl_mesh()
        self._msize = int(self.mesh.shape["mediator"])
        self._model_size = model_axis_size(self.mesh)
        if adaptive_aug_alpha is not None and aug_plan is None:
            raise ValueError("adaptive_aug_alpha requires an initial aug_plan "
                             "(the in-round hook must be installed at trace "
                             "time)")
        self._adaptive_alpha = adaptive_aug_alpha

        capacity = min(cfg.clients_per_round, data.num_clients)
        if hasattr(data, "client_images"):
            sizes = [x.shape[0] for x in data.client_images]
            pad = _pad_multiple(max(sizes), cfg.local.batch_size)
            # packed ONCE into the placement-policy store (replicated
            # buffers, client-sharded buffers, host RAM, or a disk/mmap
            # spill tier -- see core/client_store.py). With online
            # augmentation the store holds the RAW clients: the warped
            # copies only ever exist inside the round program.
            xs, ys, mask = data.padded(pad)
            self.store = build_client_store(
                cfg.store, xs, ys, mask, self.mesh, capacity=capacity,
                exchange=cfg.store_exchange,
                prefetch_depth=cfg.store_prefetch_depth,
                lru_rows=cfg.store_lru_rows)
        else:
            # streaming federation (row-source protocol, e.g.
            # data.synthetic.StreamingFederation): clients are fetched /
            # synthesized on demand by the streaming stores -- the
            # federation is never materialized, so only the policies with
            # O(c) residency can serve it
            if cfg.store not in ("host", "spilled"):
                raise ValueError(
                    f"streaming federations require the 'host' or 'spilled' "
                    f"client store, got {cfg.store!r}")
            if data.pad % cfg.local.batch_size:
                raise ValueError(
                    f"streaming federation pad {data.pad} is not a multiple "
                    f"of batch_size {cfg.local.batch_size}")
            self.store = build_client_store(
                cfg.store, mesh=self.mesh, capacity=capacity, source=data,
                prefetch_depth=cfg.store_prefetch_depth,
                lru_rows=cfg.store_lru_rows)
        self.store.telemetry = self.telemetry
        self._raw_counts = data.client_counts()
        self._counts = self._raw_counts
        self._rng = np.random.default_rng(cfg.seed)
        # pre-drawn future selections, oldest first (ensure_schedule keeps
        # this filled to the store's prefetch depth)
        self._pending_sels: deque = deque()
        # the next round's schedule, launched and read by run_round while
        # the round before it runs (None until then)
        self._ahead: _CohortSchedule | None = None

        # ---- params: model-axis sharded at rest, replicated otherwise ----
        # On a 2-D mesh each device holds 1/model of every rule-table-
        # sharded leaf (§8); on the 1-D mesh (or without param specs) the
        # params are committed replicated up front -- round outputs carry
        # the same sharding either way, so the second round never
        # cache-misses the executable.
        replicated = replicated_sharding(self.mesh)
        self._param_shardings = None
        if self._model_size > 1 and model.param_specs is not None:
            self._param_shardings = param_shardings(
                model.param_specs(), self.mesh, model_only_rules())
        placement = self._param_shardings if self._param_shardings is not None \
            else replicated
        self.params = jax.device_put(model.init(jax.random.PRNGKey(cfg.seed)),
                                     placement)
        # report the model axis the params are ACTUALLY sharded over: a
        # spec-less model stays fully replicated even on a 2-D mesh
        self.store.note_param_residency(
            _per_device_param_bytes(self.params),
            self._model_size if self._param_shardings is not None else 1)
        self.comm = CommMeter(count_params(self.params))

        # ---- §8 TP mode: shard the row compute over the model axis ----
        self._tp_rows = self._resolve_tp_rows()

        # ---- LoRA adapter exchange (models/lora.py mapping table) ----
        # With a mapping installed, self.params becomes the FROZEN
        # backbone: the round's donated arg-0 state is the flat adapter
        # dict, the backbone + the seeded frozen-A bases ride as trailing
        # value-swap operands (the aug_args pattern), and only adapter
        # bytes are charged on the WAN ledger.
        self._lora_mapping = None
        self._lora_a = None
        self.adapters = None
        self._merge_fn = None
        self.num_merge_traces = 0           # merged_params (re)compilations
        if cfg.lora_rank is not None:
            if model.param_specs is None:
                raise ValueError(
                    "lora_rank requires a model with param_specs (the "
                    "adapter mapping table is built from its LogicalParam "
                    "tree)")
            mapping = lora_lib.build_mapping(model.param_specs(),
                                             cfg.lora_rank, cfg.lora_alpha)
            self._lora_mapping = mapping
            a_key = jax.random.fold_in(jax.random.PRNGKey(cfg.seed),
                                       lora_lib.A_SALT)
            self._lora_a = jax.device_put(
                lora_lib.init_adapter_A(a_key, mapping), replicated)
            self.adapters = jax.device_put(
                lora_lib.init_adapter_state(mapping, self.params), replicated)
            # every model-exchange leg now ships the adapter payload; the
            # meter books it under wan_adapter_bytes and keeps the
            # full-size counterfactual for the scrapeable reduction ratio
            self.comm.adapter_payload_bytes = lora_lib.exchange_nbytes(
                mapping, self.comm.bytes_per_param)

            def _merge(backbone, a_tree, state):
                self.num_merge_traces += 1      # python: trace-time only
                return lora_lib.merge_params(backbone, a_tree, state,
                                             mapping)

            self._merge_fn = jax.jit(_merge)

        # ---- online-rebalancing plan (Alg. 2, device-resident mode) ----
        self._aug_plan = None
        self.last_plan: np.ndarray | None = None
        if aug_plan is not None:
            plan_np = np.asarray(aug_plan)
            if plan_np.shape != (data.num_classes,):
                raise ValueError(
                    f"aug_plan shape {plan_np.shape} != ({data.num_classes},)")
            self._install_plan(plan_np)
            # the plan broadcast is WAN traffic: (num_classes,) int32 down
            # to every client, once at initialization (adaptive refreshes
            # re-broadcast to each round's cohort in _pack_schedule)
            self.comm.plan_broadcast(plan_np.size, data.num_clients)
        self.history: list[dict] = []
        self.last_schedule_stats: dict | None = None
        # the current schedule's client groups (schedule order) -- the
        # client-level straggler model derives durations from membership
        self.last_groups: list[list[int]] | None = None
        self.num_schedule_packs = 0             # host packing events (bench)
        self.num_schedules_ahead = 0            # rounds whose schedule was ready
        self.num_round_traces = 0               # round_fn (re)compilations
        # one entry per (re)trace with its *reason* -- "initial" for each
        # entry point's first compile, "retrace" for anything after; the
        # metrics registry surfaces the retrace count as engine health
        self.trace_log: list[dict] = []
        self._schedule: tuple | None = None
        self._round = 0
        self._wave_fns: dict[int, Callable] = {}    # width -> sliced wave_fn
        # the last sync round's online Alg. 2 warp: slots warped and the
        # capacity that warped them, int32 device scalars the round path
        # never reads (None until an augmented sync round has run)
        self.last_warp_slots: jax.Array | None = None
        self.last_warp_capacity: jax.Array | None = None
        self._round_program = self._build_round_fn(loss_fn)

    # ------------------------------------------------------------------
    # round program
    # ------------------------------------------------------------------
    def _install_plan(self, plan_np: np.ndarray) -> None:
        """(Re)place the Alg. 2 plan operand and rescale the Alg. 3 counts.

        The plan is a true argument of the jitted round (same shape/dtype/
        sharding every time), so swapping its *value* -- the adaptive
        per-reschedule path -- reuses the one compiled executable."""
        plan_np = np.asarray(plan_np)
        self.last_plan = plan_np
        self._aug_plan = jax.device_put(jnp.asarray(plan_np, jnp.int32),
                                        replicated_sharding(self.mesh))
        # Alg. 3 packs mediators by the histograms clients WILL train on:
        # the expected post-augmentation counts (the materialized mode sees
        # the same thing through its inflated client data)
        self._counts = self._raw_counts * (1.0 + plan_np.astype(np.float64))

    def _resolve_tp_rows(self) -> bool:
        """Resolve ``cfg.tp_rows`` against the mesh and backend.

        TP row compute only exists when the params actually shard over a
        model axis; ``"auto"`` additionally requires a TPU/GPU backend
        because the XLA-CPU partitioner crashes on ``lax.scan`` under
        partial-auto shard_map (§8) -- CPU always falls back to the
        gather->replicated-compute oracle.  An explicit ``True`` on an
        unsupported backend raises instead of silently downgrading."""
        mode = self.cfg.tp_rows
        if mode is False or self._model_size <= 1 \
                or self._param_shardings is None:
            return False
        supported = jax.default_backend() in ("tpu", "gpu")
        if mode == "auto":
            return supported
        if not supported:
            raise ValueError(
                f"tp_rows=True needs a TPU/GPU backend, got "
                f"{jax.default_backend()!r}: the XLA-CPU partitioner "
                "crashes on lax.scan under partial-auto shard_map (§8). "
                "Use tp_rows='auto' to fall back to the gather oracle.")
        return True

    def aug_args(self) -> tuple:
        """The round executable's trailing Alg. 2 operand (empty if the
        engine holds no plan). Callers of ``wave_fn`` append this."""
        return (self._aug_plan,) if self._aug_plan is not None else ()

    def lora_args(self) -> tuple:
        """The round executable's trailing LoRA operands: the frozen
        backbone and the seeded A bases (empty without a mapping).  Pure
        value swaps -- same shapes/dtypes/shardings every round, so
        reschedules and backbone refreshes never re-trace."""
        if self._lora_mapping is None:
            return ()
        return (self.params, self._lora_a)

    def extra_args(self) -> tuple:
        """All trailing value-swap operands of the round/wave executables
        (Alg. 2 plan first, then the LoRA backbone + A)."""
        return self.aug_args() + self.lora_args()

    @property
    def server_state(self):
        """The trainable surface the round folds into: the flat adapter
        dict under LoRA, the full params otherwise."""
        if self._lora_mapping is not None:
            return self.adapters
        return self.params

    @server_state.setter
    def server_state(self, value):
        if self._lora_mapping is not None:
            self.adapters = value
        else:
            self.params = value

    def merged_params(self):
        """Evaluation-ready weights: the jitted merge-to-backbone value
        swap under LoRA (one trace for the engine's lifetime --
        ``num_merge_traces``), the params themselves otherwise."""
        if self._lora_mapping is None:
            return self.params
        return self._merge_fn(self.params, self._lora_a, self.adapters)

    def replicate_params(self, params: PyTree) -> PyTree:
        """Gather model-axis-sharded params to model-replicated (inside a
        jitted program). Identity on a 1-D mesh -- the gather/reshard pair
        moves exact bytes, which is what keeps 2-D trajectories bitwise."""
        if self._param_shardings is None:
            return params
        return jax.lax.with_sharding_constraint(
            params, replicated_sharding(self.mesh))

    def shard_params(self, params: PyTree) -> PyTree:
        """Reshard params back onto the model axis (inverse of
        ``replicate_params``; identity on a 1-D mesh)."""
        if self._param_shardings is None:
            return params
        return jax.lax.with_sharding_constraint(params, self._param_shardings)

    def _note_trace(self, fn: str) -> None:
        """Python side effect inside the jitted bodies: runs at TRACE time
        only, counting (re)compilations and recording why -- the first
        trace per entry point is expected ("initial"); anything after is
        an unexpected "retrace" (a shape/dtype/sharding drift)."""
        self.num_round_traces += 1
        first = not any(t["fn"] == fn for t in self.trace_log)
        self.trace_log.append({"fn": fn, "round": self._round,
                               "trace_index": self.num_round_traces,
                               "reason": "initial" if first else "retrace"})

    def _build_round_fn(self, loss_fn):
        cfg, store = self.cfg, self.store
        parallel_clients = cfg.aggregate == "weights"
        lora_on = self._lora_mapping is not None

        def _updates_for(model):
            if parallel_clients:
                return make_client_update(model, self.opt, cfg.local,
                                          loss_fn=loss_fn)
            return make_mediator_update(model, self.opt, cfg.local,
                                        cfg.mediator_epochs, loss_fn=loss_fn)

        # without LoRA the update program is fixed at build time; with it,
        # the per-row program trains the ADAPTER tree through a model whose
        # apply merges into the traced backbone, so the update closures are
        # built at trace time (once -- the round is traced once)
        base_update = None if lora_on else _updates_for(self.model)
        P_med = P("mediator")
        use_aug = self._aug_plan is not None
        n_aug = 1 if use_aug else 0

        def _each_row(fn, *batched):
            if cfg.row_exec == "map":
                return jax.lax.map(lambda args: fn(*args), batched)
            return jax.vmap(fn)(*batched)

        def _rows(fn, params, *batched):
            return _each_row(lambda *row: fn(params, *row), *batched)

        def _augment(draw_row, keys, x, y, m, aplan):
            # Alg. 2 for every row this device trains: draw each row's
            # slots (row by row, as the rows train), then ONE compacted
            # warp of the masked-in slots drawn as augmented -- a
            # masked-out slot's loss weight is 0, so its content is never
            # read. Under row_exec="map" this holds every row's drawn
            # slots at once, no more than the slot gather's xs holds.
            def body(keys, x, y, m, aplan):
                with jax.named_scope("alg2_warp"):
                    d = _each_row(
                        lambda k, xr, yr, mr: draw_row(k, xr, yr, mr, aplan),
                        keys, x, y, m)
                    flags = d.is_aug & (m > 0)
                    flat = (-1,) + x.shape[flags.ndim:]
                    ax, slots, cap = augmentation.compact_warp(
                        d.x.reshape(flat), d.mats.reshape(-1, 2, 2),
                        d.trans.reshape(-1, 2), flags, impl=cfg.warp_impl)
                    return ax.reshape(x.shape), d.y, slots, cap

            if self._tp_rows:
                # under TP rows the model axis stays compiler-auto, and XLA
                # cannot partition a Mosaic kernel (the warp): run Alg. 2
                # manual over it -- every model column augments the same
                # replicated client slots (the mesh is the enclosing
                # region's)
                body = jax.shard_map(body, in_specs=P(), out_specs=P(),
                                     axis_names={"model"}, check_vma=False)
            return body(keys, x, y, m, aplan)

        # the augmentation stream forks off the row's round key with a
        # salt, leaving the training stream (split from the same key
        # inside the update) untouched; a mediator row splits it once
        # more, one key per client
        def _draw_client(k, x, y, m, aplan):
            return augmentation.online_draw(
                jax.random.fold_in(k, augmentation.AUG_SALT), x, y, m, aplan)

        def _draw_mediator(k, x, y, m, aplan):
            aks = jax.random.split(
                jax.random.fold_in(k, augmentation.AUG_SALT), x.shape[0])
            return jax.vmap(
                lambda kk, xc, yc, mc: augmentation.online_draw(
                    kk, xc, yc, mc, aplan))(aks, x, y, m)

        def _train(state, data, plan, slot, keys, *extra):
            # plan/slot/keys arrive as this device's (M_local, ...) shards;
            # the store resolves them against its resident client buffers.
            # extra carries the value-swap operands: the replicated
            # (num_classes,) Alg. 2 plan when augmenting, then the LoRA
            # (backbone, a_tree) pair when a mapping is installed -- in
            # which case arg-0 `state` is the flat adapter dict and the
            # update closures train it through the merged-apply model.
            # Returns the rows' outputs, their Eq. 6 weights, and with
            # Alg. 2 the device's warp count and capacity as (1,) arrays.
            aug = extra[:n_aug]
            if lora_on:
                backbone, a_tree = extra[n_aug:]
                mapping = self._lora_mapping
                merged = dc_replace(
                    self.model,
                    apply=lambda tp, x, **kw: self.model.apply(
                        lora_lib.merge_params(backbone, a_tree, tp, mapping),
                        x, **kw))
                update = _updates_for(merged)
            else:
                update = base_update

            def local_train(p, x, y, m, k):
                with jax.named_scope("local_train"):
                    return update(p, x, y, m, k)

            with jax.named_scope("slot_gather"):
                xs, ys, ms_raw = store.slot_data(data, plan)
            if parallel_clients:
                xs, ys = xs[:, 0], ys[:, 0]
                ms = ms_raw[:, 0] * slot[:, :1]
                draw_row, slot_axes = _draw_client, (1,)
            else:
                ms = ms_raw * slot[..., None]
                draw_row, slot_axes = _draw_mediator, (1, 2)
            weights = ms.sum(axis=slot_axes)
            warp = ()
            if use_aug:
                (aplan,) = aug
                # Eq. 6 over the expected post-augmentation sizes
                weights = (ms * (1.0 + aplan.astype(jnp.float32)[ys])
                           ).sum(axis=slot_axes)
                xs, ys, slots, cap = _augment(draw_row, keys, xs, ys, ms,
                                              aplan)
                warp = (slots[None], cap[None])
            outs = _rows(local_train, state, xs, ys, ms, keys)
            return outs, weights, warp

        aug_specs = (P(),) if use_aug else ()
        # LoRA trailing operands: backbone + frozen A, replicated over the
        # mediator axis (under TP rows the backbone's model sharding rides
        # the compiler-auto model axis; under the gather oracle it arrives
        # model-replicated -- round_fn gathers it first)
        lora_specs = (P(), P()) if lora_on else ()
        # §8: with TP rows only the mediator axis is manual -- the model
        # axis stays compiler-auto so the row forward/backward runs truly
        # tensor-parallel (never materializing the full replica); otherwise
        # every mesh axis is manual (identical replicated-compute columns,
        # and partial-auto would trip the XLA-CPU scan crash)
        manual = ("mediator",) if self._tp_rows \
            else tuple(self.mesh.axis_names)
        train = mediator_shard_map(
            _train, self.mesh,
            in_specs=(P(), store.data_specs, store.plan_specs,
                      P_med, P_med) + aug_specs + lora_specs,
            out_specs=(P_med, P_med, (P_med, P_med) if use_aug else ()),
            manual_axes=manual)

        def trained_rows(state, data, plan, unperm, slot, keys, *extra):
            # -> (stacked, weights, warp); warp is () without Alg. 2, else
            # the round's warped-slot count and the summed capacity
            stacked, weights, warp = train(state, data, plan, slot, keys,
                                           *extra)
            warp = tuple(w.sum() for w in warp)
            if store.permutes_rows:             # undo locality placement
                stacked = jax.tree.map(lambda a: a[unperm], stacked)
                weights = weights[unperm]
            # replicate the (M, ...) stack before Eq. 6 so the reduction
            # order (and hence the result, bitwise) is mesh-independent
            rep = replicated_sharding(self.mesh)
            stacked = jax.lax.with_sharding_constraint(stacked, rep)
            weights = jax.lax.with_sharding_constraint(weights, rep)
            return stacked, weights, warp

        def _prep(state, extra):
            # the pre-shard_map gathers of the gather oracle: replicate
            # the model-sharded weights (arg-0 params, or the LoRA
            # backbone operand) so the fully-manual region sees them
            # whole.  Under TP rows both stay model-sharded -- that is
            # the point -- and on a 1-D mesh both are identities.
            if self._tp_rows:
                return state, extra
            if lora_on:
                backbone, a_tree = extra[n_aug:]
                return state, extra[:n_aug] + (
                    self.replicate_params(backbone), a_tree)
            return self.replicate_params(state), extra

        def round_fn(state, data, plan, unperm, slot, keys, *extra):
            self._note_trace("round_fn")        # python: counts (re)traces
            state, extra = _prep(state, extra)          # §8: model gather
            stacked, weights, warp = trained_rows(state, data, plan, unperm,
                                                  slot, keys, *extra)
            with jax.named_scope("eq6"):
                agg = self._aggregate(stacked, weights)
            with jax.named_scope("fold"):
                return self._fold(state, agg), warp

        def wave_fn(state, data, plan, unperm, slot, keys, *extra):
            # the wave-partitioned entry point (core/async_engine.py): the
            # SAME full padded-M program, stopping before aggregation. The
            # caller zeroes the slot rows of mediators outside the wave
            # (exact no-ops, like dummy mediators), so one trace serves
            # every wave of every reschedule. No donation: the dispatch
            # snapshot state is shared by all waves of a round.
            self._note_trace("wave_fn")         # python: counts (re)traces
            state, extra = _prep(state, extra)          # §8: model gather
            return trained_rows(state, data, plan, unperm, slot, keys,
                                *extra)[:2]

        self.wave_fn = jax.jit(wave_fn)

        def make_sliced_wave_fn(m_rows: int):
            # the overlapped dispatch path (wave_fn_for): the SAME row
            # program over an (m_rows, ...) slice of the packed schedule
            # instead of the masked full padded-M stack -- a W-wave round
            # then costs ~1x the sync round's row compute instead of Wx.
            # Each width is its own entry point with its own "initial"
            # trace, so the per-shape zero-retrace contract is auditable
            # in trace_log.
            tag = f"wave_fn[{m_rows}]"

            def sliced(state, data, plan, unperm, slot, keys, *extra):
                self._note_trace(tag)       # python: counts (re)traces
                state, extra = _prep(state, extra)      # §8: model gather
                return trained_rows(state, data, plan, unperm, slot, keys,
                                    *extra)[:2]

            return jax.jit(sliced)

        self._make_wave_fn = make_sliced_wave_fn
        donate = (0,) if cfg.donate_params else ()
        return jax.jit(round_fn, donate_argnums=donate)

    def _round_fn(self, state, *args) -> PyTree:
        """One sync round program: the new server state, keeping the
        round's warp count and capacity as ``last_warp_*``."""
        state, warp = self._round_program(state, *args)
        if warp:
            self.last_warp_slots, self.last_warp_capacity = warp
        return state

    def wave_fn_for(self, m_rows: int) -> Callable:
        """The sliced wave executable over ``m_rows`` schedule rows.

        ``wave_fn`` restricted to one wave: plan/slot/keys arrive as
        ``(m_rows, ...)`` row slices of the packed schedule (``m_rows`` a
        multiple of the mediator mesh size, identity ``unperm``); the
        data operands are the store's full resident buffers, unchanged.
        One executable is compiled per distinct width and cached for the
        engine's lifetime under trace tag ``wave_fn[m_rows]``.

        Row-permuting stores (``sharded``) route each row's gathers by
        its device position in the FULL schedule, so their rows cannot be
        re-sliced without replanning -- callers must fall back to the
        masked full-M ``wave_fn`` (the async engine does).
        """
        if self.store.permutes_rows:
            raise ValueError(
                f"the {self.store.policy!r} store routes gathers by row "
                "position; sliced wave executables need a non-permuting "
                "store (use the masked wave_fn instead)")
        if m_rows < 1 or m_rows % self._msize:
            raise ValueError(
                f"wave width {m_rows} must be a positive multiple of the "
                f"mediator mesh size {self._msize}")
        fn = self._wave_fns.get(m_rows)
        if fn is None:
            fn = self._make_wave_fn(m_rows)
            self._wave_fns[m_rows] = fn
        return fn

    def _fold(self, state, agg) -> PyTree:
        """Fold the Eq. 6 aggregate into the server state -- the shared
        tail of the sync round and the async commit, so S=0 async stays
        bitwise equal to sync by construction.

        Without LoRA this is the historical params fold: take the
        aggregate outright under weight aggregation, else add the delta to
        the (model-replicated) params, and reshard onto the model axis.
        Under LoRA the state is the replicated flat adapter dict and the
        fold is sharding-free."""
        if self._lora_mapping is not None:
            if self.cfg.aggregate == "weights":
                return agg
            return jax.tree.map(lambda s, d: s + d, state, agg)
        if self.cfg.aggregate == "weights":
            return self.shard_params(agg)
        return self.shard_params(
            jax.tree.map(lambda p, d: p + d, self.replicate_params(state),
                         agg))

    def _aggregate(self, stacked: PyTree, weights: jax.Array) -> PyTree:
        """Eq. 6 over the stacked (M, ...) mediator results."""
        return eq6_aggregate(stacked, weights, self.mesh,
                             use_kernel_agg=self.cfg.use_kernel_agg)

    # ------------------------------------------------------------------
    # scheduling (host side: tiny integer work, no sample movement)
    # ------------------------------------------------------------------
    def _next_selection(self) -> np.ndarray:
        """The next cohort to schedule: the oldest pre-drawn selection, else
        a fresh draw. Cohorts are consumed in draw order, so round r's is
        always the (r+1)-th ``choice`` call however early it was drawn."""
        if self._pending_sels:
            return self._pending_sels.popleft()
        c = min(self.cfg.clients_per_round, self.data.num_clients)
        return self._rng.choice(self.data.num_clients, size=c, replace=False)

    def _launch_schedule(self, sel: np.ndarray, round_idx: int,
                         read: bool = False) -> _CohortSchedule:
        """Start round ``round_idx``'s schedule for cohort ``sel``: the
        adaptive plan it implies, and Alg. 3 with the Fig. 7 statistics
        enqueued on the device as one launch, left unread unless ``read``
        (a schedule built on the spot). Changes no engine state, so it may
        run while an earlier round is in flight.

        With ``adaptive_aug_alpha`` the plan is recomputed from the
        *selected cohort's* label histograms (the drifted view of the
        federation this round trains on) and Alg. 3 packs by the expected
        post-augmentation counts it gives; ``_pack_schedule`` installs and
        re-broadcasts it when its round runs."""
        cfg = self.cfg
        plan = None
        if self._adaptive_alpha is not None:
            plan = augmentation.augmentation_plan(
                self._raw_counts[sel].sum(axis=0), self._adaptive_alpha)
            counts = self._raw_counts[sel] * (1.0 + plan.astype(np.float64))
        else:
            counts = self._counts[sel]
        pending = None
        with self.telemetry.span("reschedule", cohort=len(sel),
                                 schedule=cfg.schedule, round=round_idx):
            if cfg.schedule == "kld":
                pending = scheduling.launch_reschedule(
                    counts, cfg.gamma, use_kernel=cfg.reschedule_kernel)
            elif cfg.gamma > 1:
                pending = scheduling.launch_random_schedule(
                    len(sel), cfg.gamma, counts, seed=cfg.seed + round_idx)
            sched = _CohortSchedule(round_idx, sel, plan, pending)
            if read:
                self._read_schedule(sched)
        return sched

    def _read_schedule(self, sched: _CohortSchedule) -> None:
        """Read a launched schedule's groups and Fig. 7 statistics: the
        Alg. 3 picks and each mediator's KLD, copies of what the one launch
        computed; nothing for FedAvg (selection order, one client each)."""
        sel = sched.sel
        if sched.pending is None:
            sched.groups = [[int(k)] for k in sel]
            return
        meds = sched.pending.read(self.telemetry)
        sched.groups = [[int(sel[i]) for i in m.clients] for m in meds]
        sched.stats = scheduling.schedule_stats(meds, self.telemetry)

    def _pack_schedule(self, sched: _CohortSchedule) -> tuple:
        """Schedule -> store-routed gather plan, for the round about to run.

        Installs the schedule's adaptive plan (a round operand, so no
        re-trace; re-broadcast to the cohort on the WAN ledger), then
        packs the client groups into padded ``(M_pad, gamma)`` rows (rows
        assigned by the store's placement pass), remaps the gather through
        the store, and precomputes ``unperm`` -- the row order that puts
        stacked outputs back in schedule order before aggregation (real
        mediators first, dummies last), which is what keeps every
        placement bit-identical to the replicated path.
        """
        tel = self.telemetry
        if sched.plan is not None:
            with tel.span("plan_refresh", cohort=len(sched.sel)):
                self._install_plan(sched.plan)
                self.comm.plan_broadcast(sched.plan.size, len(sched.sel))
        groups = self.last_groups = sched.groups
        self.last_schedule_stats = sched.stats
        m_real = len(groups)
        m_pad = self.cfg.pad_mediators_to or m_real
        if m_pad < m_real:
            raise ValueError(
                f"pad_mediators_to={m_pad} smaller than the schedule "
                f"({m_real} mediators)")
        m_pad = _pad_multiple(m_pad, self._msize)
        with tel.span("pack", m_real=m_real, m_pad=m_pad,
                      policy=self.store.policy) as psp:
            if sched.stats:
                psp.set(kld_mean=sched.stats["kld_mean"])
            row_to_group = self.store.place(groups, m_pad)
            idx = np.zeros((m_pad, self.cfg.gamma), np.int32)
            slot = np.zeros((m_pad, self.cfg.gamma), np.float32)
            row_of = np.zeros(m_real, np.int64)
            for r, g in enumerate(row_to_group):
                if g < 0:
                    continue
                row_of[g] = r
                for ci, cid in enumerate(groups[g]):
                    idx[r, ci] = cid
                    slot[r, ci] = 1.0
            dummy_rows = np.flatnonzero(row_to_group < 0)
            unperm = np.concatenate([row_of, dummy_rows]).astype(np.int32)
            with tel.span("store_stream", policy=self.store.policy) as ssp:
                data_args, plan_args = self.store.plan(idx, slot)
                ssp.set(bytes=self.store.last_stream_bytes)
                ssp.sync_on(data_args)
            if self.store.last_stream_bytes:
                # host->device streaming is pod-side traffic: intra-pod
                # ledger only, so the WAN bytes stay invariant to placement
                self.comm.store_stream(self.store.last_stream_bytes)
            if getattr(self.store, "last_placement_stats", None):
                # store placement telemetry rides along under a store_
                # namespace: a raw merge once let colliding keys (e.g. a
                # future store "num_mediators") silently clobber the
                # scheduler's numbers
                base = self.last_schedule_stats or {}
                prefixed = {f"store_{k}": v for k, v in
                            self.store.last_placement_stats.items()}
                overlap = base.keys() & prefixed.keys()
                assert not overlap, \
                    f"schedule/store stats key collision: {sorted(overlap)}"
                self.last_schedule_stats = {**base, **prefixed}
            psp.set(stream_bytes=self.store.last_stream_bytes)
        self.num_schedule_packs += 1
        return (data_args, plan_args, jnp.asarray(unperm),
                jnp.asarray(slot), row_to_group, m_real)

    def _round_keys(self, row_to_group: np.ndarray, m_real: int,
                    round_idx: int | None = None) -> jax.Array:
        base = jax.random.fold_in(
            jax.random.PRNGKey(self.cfg.seed + 1),
            self._round if round_idx is None else round_idx)
        keys = jax.random.split(base, m_real)   # split at the REAL count
        take = np.where(row_to_group >= 0, row_to_group, 0)
        rows = jnp.asarray(keys)[jnp.asarray(take)]
        real = jnp.asarray(row_to_group >= 0)   # dummy rows: any key no-ops
        return jnp.where(real[:, None], rows, jnp.zeros_like(rows))

    # ------------------------------------------------------------------
    # driving
    # ------------------------------------------------------------------
    def ensure_schedule(self) -> tuple:
        """(Re)pack the gather schedule if this round needs one.

        A schedule that ``run_round`` built one round ahead is packed as
        it is (``num_schedules_ahead`` counts them); otherwise it is built
        here, on the spot. With a prefetch-capable store (``spilled``) and
        per-round rescheduling, the next rounds' selections are pre-drawn
        here -- up to the store's ``prefetch_depth`` ahead -- and staged in
        the background, so the spill-tier reads overlap this round's device
        compute. The rng draws happen in the same order as the eager
        path (round r's selection is always the (r+1)-th ``choice``
        call; depth and the lookahead only change how early the calls are
        issued), so trajectories are bitwise unchanged at any depth."""
        cfg = self.cfg
        if cfg.reschedule_every_round or self._schedule is None:
            sched, self._ahead = self._ahead, None
            if sched is not None and sched.round == self._round:
                self.num_schedules_ahead += 1
            else:
                sel = sched.sel if sched is not None \
                    else self._next_selection()
                sched = self._launch_schedule(sel, self._round, read=True)
            self._schedule = self._pack_schedule(sched)
            if cfg.reschedule_every_round and hasattr(self.store, "prefetch"):
                c = min(cfg.clients_per_round, self.data.num_clients)
                depth = max(1, int(getattr(self.store, "prefetch_depth", 1)))
                while len(self._pending_sels) < depth:
                    nxt = self._rng.choice(self.data.num_clients, size=c,
                                           replace=False)
                    self._pending_sels.append(nxt)
                    self.store.prefetch(nxt)
        return self._schedule

    def run_round(self) -> None:
        cfg, tel = self.cfg, self.telemetry
        c = min(cfg.clients_per_round, self.data.num_clients)
        wan0 = self.comm.total_bytes
        with tel.span("round", round=self._round, cohort=c,
                      schedule=cfg.schedule, policy=cfg.store) as rsp:
            reads0 = tel.host_reads
            data_args, plan_args, unperm, slot, row_to_group, m_real = \
                self.ensure_schedule()
            ahead = None
            if cfg.reschedule_every_round:
                # the next round's Alg. 3 and Fig. 7 go on the device queue
                # ahead of this round's program and are read once it is
                # queued: the read waits on the round before this one,
                # while this one keeps the chip busy
                ahead = self._launch_schedule(self._next_selection(),
                                              self._round + 1)
            with tel.span("round_keys"):
                keys = self._round_keys(row_to_group, m_real)
            with tel.span("round_program", mediators=m_real) as psp:
                self.server_state = self._round_fn(self.server_state,
                                                   data_args, plan_args,
                                                   unperm, slot, keys,
                                                   *self.extra_args())
                psp.sync_on(self.server_state)
            if ahead is not None:
                self._read_schedule(ahead)
                self._ahead = ahead
            if cfg.aggregate == "weights":
                self.comm.fedavg_round(c)
            else:
                self.comm.astraea_round(c, cfg.gamma, cfg.mediator_epochs)
            if self._model_size > 1 and (self._lora_mapping is None
                                         or not self._tp_rows):
                # intra-pod ledger only: the per-round model-axis param
                # gather must never pollute the bytes behind the 82% claim.
                # TP-rows + LoRA is the one mode with no gather at all (the
                # backbone stays sharded and the adapters are replicated);
                # non-LoRA gathers either in-round or at the _fold add, and
                # gather-mode LoRA gathers the backbone operand.
                self.comm.model_axis_round(self._msize * self._model_size,
                                           self._model_size)
            if self.store.exchange_bytes_per_round:
                # the sharded serve exchange executes with every round
                # program; mark the charge on the timeline too
                self.comm.store_exchange(self.store.exchange_bytes_per_round)
                tel.instant("store_exchange",
                            bytes=self.store.exchange_bytes_per_round)
            self.comm.end_round()
            self._round += 1
            rsp.set(wan_bytes=self.comm.total_bytes - wan0,
                    traces=self.num_round_traces,
                    host_reads=tel.host_reads - reads0)
        tel.observe_round(self, duration_s=rsp.duration_s)

    def fit(self, rounds: int, eval_every: int = 10) -> list[dict]:
        for _ in range(rounds):
            self.run_round()
            if self._round % eval_every == 0 or self._round == rounds:
                m = evaluate(self.model, self.merged_params(),
                             self.data.test_images, self.data.test_labels)
                m.update(round=self._round, traffic_mb=self.comm.megabytes)
                if self.last_schedule_stats and \
                        "kld_mean" in self.last_schedule_stats:
                    m["mediator_kld_mean"] = self.last_schedule_stats["kld_mean"]
                self.history.append(m)
        return self.history
