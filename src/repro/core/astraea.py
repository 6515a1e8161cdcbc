"""The Astraea server: rebalance -> reschedule -> train -> aggregate.

Workflow (paper Fig. 3):

1. **Initialization** -- clients report label histograms; server computes
   the global distribution.
2. **Rebalancing** (Alg. 2) -- server broadcasts the per-class
   augmentation plan; clients augment locally (random affine warps).
3. Each synchronization round: sample ``c`` online clients, run Alg. 3 to
   greedily pack them into mediators of <= gamma clients (min KLD to
   uniform), train every mediator in parallel (clients sequential inside,
   E_m mediator epochs), and FedAvg-aggregate the mediator deltas with
   weights n_m / n.

``aug_mode`` picks where step 2 executes:

* ``"online"`` (default) -- the plan is handed to the round engine and the
  resample+warp runs inside the jitted round program, redrawn every round
  (``augmentation.online_draw``, ``compact_warp``).  No augmented copy is
  ever materialized: client stores keep the raw federation (zero extra device
  storage), and Alg. 3 / Eq. 6 run on the expected post-augmentation
  histograms.  ``planned_extra_frac`` reports what the paper's Fig. 9
  storage cost *would have been*.
* ``"materialized"`` -- the historical pre-training host phase: every
  augmentation is generated up front and the federation rebuilt (the
  paper's deployment, with its ``extra_storage_frac`` cost).  Kept as the
  equivalence oracle for online mode.

The round itself is executed by ``core.engine.FLRoundEngine`` (the
device-resident, mediator-sharded round program); this class owns the
paper-specific rebalancing phase and presents the historical trainer API.
Aggregation uses the ``fedavg_agg`` Pallas kernel path when
``use_kernel_agg``; default is the pure-jnp ``weighted_average``.
"""
from __future__ import annotations

from dataclasses import dataclass, field

from repro.core import augmentation
from repro.core.augmentation import AUG_MODES
from repro.core.engine import EngineConfig, FLRoundEngine
from repro.core.fl import LocalSpec
from repro.data.federated import FederatedDataset
from repro.models.cnn import Model
from repro.optim.optimizers import Optimizer


@dataclass
class AstraeaTrainer:
    model: Model
    opt: Optimizer
    data: FederatedDataset
    clients_per_round: int                  # c
    gamma: int                              # max clients per mediator
    local: LocalSpec                        # B, E
    mediator_epochs: int = 1                # E_m
    alpha: float | None = 0.67              # augmentation factor; None = NoAug
    aug_mode: str | None = "online"         # "online" | "materialized" | None
    # per-round adaptive rebalancing: recompute the Alg. 2 plan from the
    # selected cohort's label histograms at every reschedule (online mode
    # only; the refreshed plan is re-broadcast and metered per reschedule)
    adaptive_plan: bool = False
    use_kernel_agg: bool = False
    reschedule_every_round: bool = False    # static client data -> schedule once
    store: str = "replicated"               # client-store placement policy
    store_exchange: str = "ragged"          # sharded serve exchange mode
    # padded mediator count; defaults to ceil(c / gamma) -- the exact output
    # size of Alg. 3 -- so reschedules never re-jit the round executable
    pad_mediators_to: int | None = None
    # bounded-staleness async rounds (core/async_engine.py); None = the
    # synchronous barrier engine
    async_spec: object = None
    mesh: object = None                     # mediator mesh; None = all devices
    # model-axis size of the 2-D (mediator, model) mesh: each mediator
    # slice tensor-shards its parameter residency over this many devices
    # (launch/mesh.py:make_fl_mesh). None = 1-D mediator mesh (or the
    # ASTRAEA_MODEL_PARALLEL env default). Ignored when ``mesh`` is given.
    model_parallel: int | None = None
    # true tensor-parallel row compute over the model axis (§8 TP mode);
    # "auto" = on for TPU/GPU backends, gather oracle elsewhere
    tp_rows: object = "auto"
    # LoRA adapter-delta WAN exchange: adapter mapping-table rank built
    # from model.param_specs() (models/lora.py); None = full-delta legs
    lora_rank: int | None = None
    lora_alpha: float | None = None
    # optional obs.Telemetry handle threaded into the engine (host-side
    # spans + metrics; None = the zero-cost no-op stubs)
    telemetry: object = None
    seed: int = 0
    history: list[dict] = field(default_factory=list)

    def __post_init__(self):
        # ---- Rebalancing phase (Alg. 2), shared with FedAvgTrainer ----
        phase = augmentation.resolve_aug_mode(self.data, self.alpha,
                                              self.aug_mode, self.seed)
        self.data = phase.data
        self.augmentation_plan = phase.plan
        self.extra_storage_frac = phase.extra_storage_frac  # realized
        self.planned_extra_frac = phase.planned_extra_frac  # avoided (online)
        engine_plan, adaptive_alpha = augmentation.resolve_engine_plan(
            phase, self.adaptive_plan, self.alpha)
        from repro.launch.mesh import resolve_fl_mesh
        mesh = resolve_fl_mesh(self.mesh, self.model_parallel)

        # donate_params=False: the historical trainer API let callers keep
        # references to trainer.params across rounds; donation (the engine
        # default) would invalidate those buffers on accelerators
        c_eff = min(self.clients_per_round, self.data.num_clients)
        pad_m = self.pad_mediators_to or -(-c_eff // self.gamma)
        self.engine = FLRoundEngine(
            self.model, self.opt, self.data,
            EngineConfig.astraea(
                clients_per_round=self.clients_per_round, gamma=self.gamma,
                local=self.local, mediator_epochs=self.mediator_epochs,
                use_kernel_agg=self.use_kernel_agg,
                reschedule_every_round=self.reschedule_every_round,
                store=self.store, store_exchange=self.store_exchange,
                pad_mediators_to=pad_m, tp_rows=self.tp_rows,
                lora_rank=self.lora_rank, lora_alpha=self.lora_alpha,
                donate_params=False, seed=self.seed),
            mesh=mesh, aug_plan=engine_plan,
            adaptive_aug_alpha=adaptive_alpha, telemetry=self.telemetry)
        if phase.mode == "materialized":
            # online mode charges this inside the engine; the materialized
            # phase broadcast the same plan before the engine existed
            self.engine.comm.plan_broadcast(self.data.num_classes,
                                            self.data.num_clients)
        if self.async_spec is not None:
            from repro.core.async_engine import AsyncRoundEngine
            self.runner = AsyncRoundEngine(self.engine, self.async_spec)
        else:
            self.runner = self.engine
        self.history = self.runner.history

    # ---- historical trainer surface, delegated to the engine ----
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
    def last_schedule_stats(self):
        return self.engine.last_schedule_stats

    @property
    def _round(self):
        return self.engine._round

    @_round.setter
    def _round(self, value):
        self.engine._round = value

    def run_round(self) -> None:
        self.runner.run_round()

    def fit(self, rounds: int, eval_every: int = 10) -> list[dict]:
        return self.runner.fit(rounds, eval_every)
