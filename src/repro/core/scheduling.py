"""Algorithm 3 — Mediator based multi-client rescheduling.

Greedy strategy: a mediator repeatedly absorbs the unassigned client whose
label histogram brings the mediator's *merged* distribution closest to
uniform (min ``D_KL(P_m + P_k || P_u)``), until it holds ``gamma`` clients;
then a fresh mediator is created, until no clients remain.

Two implementations, identical mediator lists:

* ``impl="batched"`` (default) — one jitted ``lax.scan`` over the K
  absorption steps with ``client_counts`` held device-resident and
  assigned clients masked to ``+inf``; a single device roundtrip per
  reschedule. The historical per-step dispatch (one
  ``merged_kld_scores`` call + host argmin per absorbed client) cost
  O(K) roundtrips — O(K^2) score work issued from the interpreter — and
  stalled Alg. 3 for minutes at K=1e5. The same launch sums each
  mediator's histogram from the picks and scores it for Fig. 7
  (``alg3_fig7``), so picks and statistics come from one launch;
  ``launch_reschedule`` enqueues it and leaves the reads for later, which
  lets the engine build a round's schedule while the chip runs the round
  before. With ``use_kernel=True`` the
  whole pass instead runs as ONE Pallas launch
  (``kernels.kld_greedy_picks``): the masked-argmin sweep, pick commit,
  histogram fold and gamma-reset all live in kernel scratch, so a
  scheduling pass issues O(1) ``pallas_call``s instead of the historical
  O(M·gamma) per-step kernel dispatches.
* ``impl="loop"`` — the numpy greedy loop (exact Alg. 3 as in the paper;
  kept as the equivalence oracle, and as the path that drives the Pallas
  ``kld_score`` kernel one launch per greedy step via
  ``use_kernel=True`` — the historical O(M·gamma)-launch pattern).

All paths tie-break identically: the loop's ``argmin`` returns the first
minimum over the unassigned list, which stays in ascending client order;
the masked argmin (scan and kernel alike) returns the lowest client id
among the minima. Scores match bitwise because every path casts counts
to f32 and replays the same ``merged_kld_scores`` op sequence, and label
counts are integer-valued (< 2^24), where f32 accumulation is exact.

We also provide ``random_schedule`` (the FedAvg-style control: clients
grouped arbitrarily) for the ablations in EXPERIMENTS.md.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import distribution as dist
from repro.obs.telemetry import as_telemetry, host_read


@dataclass
class Mediator:
    """One mediator's schedule: ordered client ids + merged label counts,
    and, when its Alg. 3 launch computed it, its Fig. 7 ``D_KL(P_m ||
    P_u)`` still on the device (``kld``)."""
    clients: list[int] = field(default_factory=list)
    counts: np.ndarray | None = None
    kld: jax.Array | None = None

    def kld_to_uniform(self, telemetry=None) -> float:
        kld = self.kld if self.kld is not None \
            else dist.kld_to_uniform(jnp.asarray(self.counts))
        return host_read(telemetry, "kld", float, kld)


def _score_candidates(mediator_counts: np.ndarray, candidate_counts: np.ndarray,
                      *, use_kernel: bool = False,
                      telemetry=None) -> np.ndarray:
    """D_KL(normalize(P_m + P_k) || U) for every candidate k."""
    med = jnp.asarray(mediator_counts, jnp.float32)
    cand = jnp.asarray(candidate_counts, jnp.float32)
    if use_kernel:
        from repro.kernels import ops as kops
        scores = kops.kld_score(med, cand)
    else:
        scores = dist.merged_kld_scores(med, cand)
    return host_read(telemetry, "alg3_scores", np.asarray, scores)


@partial(jax.jit, static_argnames="gamma")
def _greedy_picks(counts_f32: jnp.ndarray, gamma: int) -> jnp.ndarray:
    """Device-resident Alg. 3 inner loop: the full absorption order.

    One ``lax.scan`` step per absorbed client -- score every client
    against the open mediator (assigned ones masked to ``+inf``), take
    the first-minimum argmin (the loop's tie-break), absorb, and reset
    the mediator histogram after every ``gamma`` picks."""
    k = counts_f32.shape[0]

    def step(carry, _):
        assigned, med, fill = carry
        scores = dist.merged_kld_scores(med, counts_f32)
        pick = jnp.argmin(jnp.where(assigned, jnp.inf, scores))
        merged = med + counts_f32[pick]
        full = fill + 1 == gamma
        med = jnp.where(full, jnp.zeros_like(merged), merged)
        fill = jnp.where(full, 0, fill + 1)
        return (assigned.at[pick].set(True), med, fill), pick

    init = (jnp.zeros(k, bool), jnp.zeros(counts_f32.shape[1], jnp.float32),
            jnp.asarray(0, jnp.int32))
    return jax.lax.scan(step, init, None, length=k)[1]


def _mediator_klds(counts_f32: jnp.ndarray, picks: jnp.ndarray,
                   gamma: int) -> jnp.ndarray:
    """Fig. 7 on the device: ``D_KL(P_m || P_u)`` of every mediator, its
    histogram summed from its clients' (mediator ``s`` holds
    ``picks[s * gamma:(s + 1) * gamma]``; a short last one is padded with
    a zero row)."""
    k, c = counts_f32.shape
    m = -(-k // gamma)
    rows = jnp.concatenate([counts_f32, jnp.zeros((1, c), jnp.float32)])
    idx = jnp.concatenate([picks.astype(jnp.int32),
                           jnp.full((m * gamma - k,), k, jnp.int32)])
    return dist.kld_to_uniform(rows[idx].reshape(m, gamma, c).sum(axis=1))


def alg3_fig7(counts_f32: jnp.ndarray, gamma: int,
              use_kernel: bool = False) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Alg. 3 and its Fig. 7 statistics as one program: the absorption
    order (the ``lax.scan``, or the one-launch ``kld_greedy_picks`` with
    ``use_kernel``) and each mediator's KLD to uniform."""
    if use_kernel:
        from repro.kernels import ops as kops
        picks = kops.kld_greedy_picks(counts_f32, gamma)
    else:
        picks = _greedy_picks(counts_f32, gamma)
    return picks, _mediator_klds(counts_f32, picks, gamma)


# Each mediator's KLD leaves the program as a buffer of its own, so each
# ``host_read.kld`` is a plain copy of a computed value and launches nothing.
@partial(jax.jit, static_argnames=("gamma", "use_kernel"))
def _alg3_fig7(counts_f32, gamma, use_kernel):
    picks, klds = alg3_fig7(counts_f32, gamma, use_kernel)
    return picks, tuple(klds)


@partial(jax.jit, static_argnames="gamma")
def _fig7(counts_f32, order, gamma):
    return tuple(_mediator_klds(counts_f32, order, gamma))


@dataclass
class PendingSchedule:
    """A schedule on its way: the clients' order and each mediator's
    Fig. 7 KLD, computed by one device launch and not read yet."""
    counts: np.ndarray              # (K, C) histograms it was launched on
    gamma: int
    picks: Any                      # (K,) order: device (Alg. 3) or host
    klds: tuple                     # one device scalar per mediator

    def read(self, telemetry=None) -> list[Mediator]:
        """The mediators, their KLDs left on the device for
        ``schedule_stats``. Reads the Alg. 3 picks (``host_read.
        alg3_picks``); a host-drawn order needs no read."""
        picks = self.picks
        if isinstance(picks, jax.Array):
            picks = host_read(telemetry, "alg3_picks", np.asarray, picks)
        g = self.gamma
        return [Mediator(clients=[int(c) for c in picks[s:s + g]],
                         counts=self.counts[picks[s:s + g]].sum(0), kld=kld)
                for kld, s in zip(self.klds, range(0, len(picks), g))]


def launch_reschedule(client_counts: np.ndarray, gamma: int, *,
                      use_kernel: bool = False) -> PendingSchedule:
    """Alg. 3 with the Fig. 7 statistics of its mediators, enqueued on
    the device as one launch and left unread (``impl="batched"``)."""
    client_counts = np.asarray(client_counts, np.float64)
    picks, klds = _alg3_fig7(jnp.asarray(client_counts, jnp.float32),
                             gamma=int(gamma), use_kernel=use_kernel)
    return PendingSchedule(client_counts, int(gamma), picks, klds)


def launch_random_schedule(num_clients: int, gamma: int,
                           client_counts: np.ndarray,
                           seed: int = 0) -> PendingSchedule:
    """``random_schedule``'s grouping, drawn on the host, with the Fig. 7
    statistics of its mediators enqueued on the device and left unread."""
    client_counts = np.asarray(client_counts, np.float64)
    order = np.random.default_rng(seed).permutation(num_clients)
    klds = _fig7(jnp.asarray(client_counts, jnp.float32), jnp.asarray(order),
                 gamma=int(gamma))
    return PendingSchedule(client_counts, int(gamma), order, klds)


def reschedule(client_counts: np.ndarray, gamma: int, *,
               use_kernel: bool = False, impl: str = "auto",
               telemetry=None) -> list[Mediator]:
    """Alg. 3: partition clients into mediators of size <= gamma.

    Args:
      client_counts: ``(K, C)`` per-client label histograms (the only thing
        clients share -- never samples).
      gamma: max clients per mediator.
      use_kernel: run the scoring through Pallas. Under ``"batched"`` the
        ENTIRE pass is one ``kld_greedy_picks`` launch; under ``"loop"``
        the numpy loop drives one ``kld_score`` launch per greedy step
        (the historical O(M·gamma)-launch pattern, kept as an oracle).
      impl: ``"batched"`` (device-resident; one executable dispatch per
        reschedule), ``"loop"`` (numpy greedy oracle), or ``"auto"``
        (batched). All produce identical mediator lists.
      telemetry: the handle whose ``host_read`` spans name the device->host
        reads (the picks, or the loop's per-step scores); off by default.

    Returns:
      List of ``Mediator``; every client appears in exactly one. The
      batched path also sets each one's Fig. 7 ``kld``.
    """
    if impl not in ("auto", "batched", "loop"):
        raise ValueError(f"unknown reschedule impl {impl!r}")
    if impl == "auto":
        impl = "batched"
    client_counts = np.asarray(client_counts, np.float64)
    num_clients, num_classes = client_counts.shape
    if num_clients == 0:
        return []
    if impl == "batched":
        return launch_reschedule(client_counts, gamma,
                                 use_kernel=use_kernel).read(telemetry)
    unassigned = list(range(num_clients))
    mediators: list[Mediator] = []
    while unassigned:
        med = Mediator(counts=np.zeros(num_classes))
        while unassigned and len(med.clients) < gamma:
            cand = client_counts[unassigned]                      # (k, C)
            scores = _score_candidates(med.counts, cand, use_kernel=use_kernel,
                                       telemetry=telemetry)
            best = int(np.argmin(scores))
            cid = unassigned.pop(best)
            med.clients.append(cid)
            med.counts = med.counts + client_counts[cid]
        mediators.append(med)
    return mediators


def random_schedule(num_clients: int, gamma: int, client_counts: np.ndarray,
                    seed: int = 0) -> list[Mediator]:
    """Control: arbitrary grouping (what plain FedAvg round batching does)."""
    return launch_random_schedule(num_clients, gamma, client_counts,
                                  seed).read()


def place_mediators(groups: list[list[int]], num_shards: int,
                    rows_per_shard: int, owner) -> tuple[np.ndarray, dict]:
    """Locality-aware placement: mediators -> shard rows (sharded ClientStore).

    With the client store partitioned over ``num_shards`` devices, a
    mediator's ``x_all[idx]`` gather is free for clients its own device
    holds and costs an ``all_gather`` slot for every remote one. This pass
    assigns each mediator (a list of client ids) to the shard owning the
    most of its clients, subject to ``rows_per_shard`` capacity per shard --
    greedy in descending *regret* (best-shard count minus runner-up), so
    mediators with the most to lose from a bad placement pick first.
    Deterministic: ties broken by mediator index, then shard index.

    Args:
      groups: per-mediator client-id lists (scheduling order).
      num_shards: mediator mesh size ``n``.
      rows_per_shard: ``M_pad // n`` mediator rows available per shard.
      owner: callable mapping a client id to its owning shard.

    Returns:
      ``(row_to_group, stats)``: ``row_to_group`` is an ``(n * rows_per_shard,)``
      int array giving the original mediator index occupying each row
      (``-1`` = dummy row; rows ``[d * rows_per_shard, (d+1) * rows_per_shard)``
      execute on shard ``d``), and ``stats`` counts local vs cross-shard
      client fetches under this placement.
    """
    m = len(groups)
    m_pad = num_shards * rows_per_shard
    if m > m_pad:
        raise ValueError(f"{m} mediators do not fit {num_shards}x"
                         f"{rows_per_shard} shard rows")
    counts = np.zeros((m, num_shards), np.int64)
    for g, clients in enumerate(groups):
        for cid in clients:
            counts[g, owner(cid)] += 1

    def regret(g: int) -> int:
        row = np.sort(counts[g])
        return int(row[-1] - (row[-2] if num_shards > 1 else 0))

    capacity = [rows_per_shard] * num_shards
    shard_of = np.zeros(m, np.int64)
    local = 0
    for g in sorted(range(m), key=lambda g: -regret(g)):
        prefs = np.argsort(-counts[g], kind="stable")
        s = next(int(s) for s in prefs if capacity[s] > 0)
        capacity[s] -= 1
        shard_of[g] = s
        local += int(counts[g, s])
    row_to_group = np.full(m_pad, -1, np.int64)
    next_row = [d * rows_per_shard for d in range(num_shards)]
    for g in range(m):                      # mediator order within a shard
        d = int(shard_of[g])
        row_to_group[next_row[d]] = g
        next_row[d] += 1
    total = int(sum(len(c) for c in groups))
    stats = {"local_fetches": local, "remote_fetches": total - local,
             "total_fetches": total, "num_shards": num_shards}
    return row_to_group, stats


def partition_waves(durations: np.ndarray, wave_size: int
                    ) -> tuple[list[list[int]], dict]:
    """Straggler-aware wave placement for the async round engine.

    Sorts mediators by simulated duration (stable, so ties keep schedule
    order) and chunks them into waves of ``wave_size`` -- co-scheduling
    slow mediators into the *late* waves so the fast waves are never
    blocked behind a straggler. A wave completes when its slowest member
    does, so sorted chunking minimizes the sum of wave completion times
    over all contiguous partitions of a fixed wave size.

    This composes with *client*-level heterogeneity without any code
    here changing: a ``StragglerSpec(level="client")`` model derives
    each mediator's duration as the sum of its members' factors
    (``StragglerModel.durations_for_groups``), so a slow *device* drags
    whichever mediator Alg. 3 packed it into toward the late waves --
    speed-aware wave placement stacks on top of KLD-greedy packing
    rather than perturbing it. With all clients at unit speed the
    durations tie everywhere and the stable sort reproduces the
    historical mediator-only ordering bitwise.

    Args:
      durations: ``(M,)`` simulated per-mediator training times
        (schedule order; see ``core/staleness.py``).
      wave_size: mediators per wave; ``<= 0`` means one wave holding the
        whole fleet (the synchronous barrier, degenerate case).

    Returns:
      ``(waves, stats)``: ``waves`` is a list of schedule-index lists in
      completion order (fastest wave first); ``stats`` reports per-wave
      completion times, the synchronous barrier time (max duration), and
      ``blocked_time_saved`` -- the reduction in summed wave completion
      times vs chunking in arbitrary (schedule) order, i.e. what
      co-scheduling the stragglers bought.
    """
    durations = np.asarray(durations, np.float64)
    m = int(durations.shape[0])
    if m == 0:
        raise ValueError("cannot partition zero mediators into waves")
    ws = wave_size if wave_size and wave_size > 0 else m
    order = np.argsort(durations, kind="stable")
    waves = [[int(i) for i in order[s:s + ws]] for s in range(0, m, ws)]
    wave_times = [float(durations[w].max()) for w in waves]
    naive_times = [float(durations[s:s + ws].max()) for s in range(0, m, ws)]
    stats = {
        "num_waves": len(waves),
        "wave_times": wave_times,
        "barrier_time": float(durations.max()),
        "blocked_time_saved": float(sum(naive_times) - sum(wave_times)),
    }
    return waves, stats


def schedule_stats(mediators: list[Mediator],
                   telemetry=None) -> dict[str, float]:
    """Fig. 7 metrics: distribution of D_KL(P_m || P_u) over mediators.

    These keys are an observability surface, not just a return value: the
    engine stores them as ``last_schedule_stats`` (with the store's
    placement stats merged under a disjoint ``store_`` prefix) and the
    telemetry layer republishes each one as an ``astraea_schedule_<key>``
    / ``astraea_store_<key>`` gauge every round -- renaming a key here
    renames the exported metric. Each mediator's KLD is one device->host
    read (``host_read.kld``), all inside the ``schedule_stats`` span of
    ``telemetry``: a copy of the value its Alg. 3 launch computed, or, for
    a mediator without one (the loop oracle's), a ``kld_to_uniform``
    launch of its own.
    """
    telemetry = as_telemetry(telemetry)
    with telemetry.span("schedule_stats", mediators=len(mediators)):
        klds = np.array([m.kld_to_uniform(telemetry) for m in mediators])
    return {
        "kld_mean": float(klds.mean()),
        "kld_median": float(np.median(klds)),
        "kld_max": float(klds.max()),
        "kld_min": float(klds.min()),
        "num_mediators": len(mediators),
    }
