"""Algorithm 2 — Global data distribution based data augmentation.

Server side: from the global per-class counts ``C_1..C_N`` compute the mean
``C_bar``; every class with ``C_i < C_bar`` goes into the augmentation set,
and each of its samples generates ``round((C_bar / C_y) ** alpha)``
augmentations (random shift, rotation, shear, zoom).

Client side: augmentation runs *locally and in parallel* on each client --
no raw data leaves a device. We implement the four augmentation primitives
as a single random affine warp (bilinear resampling via
``jax.scipy.ndimage.map_coordinates``), which is the JAX-native equivalent
of the Keras ImageDataGenerator the paper used.

The paper's key subtlety, which we preserve exactly: the augmentation count
is a *function of the class's global count*, so a large ``alpha`` (e.g. 2)
overshoots ``C_bar`` for very-minority classes and re-imbalances the data --
EXPERIMENTS.md reproduces that failure mode.

Two execution modes share the plan math:

* **Materialized** (``rebalance_federation``) -- the historical pre-training
  phase: every augmented copy is generated up front into host numpy and the
  federation is rebuilt.  Faithful to the paper's deployment (clients store
  their augmentations, the ~24% extra-storage cost of Fig. 9) and kept as
  the equivalence oracle for the online mode.
* **Online** (``online_draw`` + ``compact_warp``) -- the device-resident
  pipeline: nothing is materialized; each round the jitted round program
  redraws a fixed-shape, class-conditional resample of every scheduled
  client's padded batch, then warps the masked-in slots drawn as
  augmented, compacted to a capacity that fits their count (the other
  slots' content never reaches a loss).  Each output slot draws its
  source sample from a seeded categorical with per-sample weights
  ``mask * (1 + n_aug[y])`` and is then warped with probability
  ``n_aug[y] / (1 + n_aug[y])`` -- so the expected class mixture of the
  draws is exactly ``planned_counts`` (normalized) and
  the expected raw-vs-warped composition matches Alg. 2's ``C_y`` originals
  + ``C_y * n_aug_y`` copies, while every shape stays static (one round
  trace).  Stores keep the *raw* clients: per-device bytes fall back to the
  pre-augmentation packed size.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import distribution as dist

Array = jax.Array


# --------------------------------------------------------------------------
# Server-side plan (Alg. 2 lines 1-6)
# --------------------------------------------------------------------------

def augmentation_plan(global_counts: np.ndarray, alpha: float) -> np.ndarray:
    """Per-class number of augmentations per existing sample.

    Returns an int array ``(num_classes,)``: 0 for classes at/above the mean
    (not in the augmentation set), else ``round((C_bar / C_i) ** alpha)``.

    Alg. 2 line 3 edge case, handled explicitly: a class with ``C_i == 0``
    is *below* the mean (it enters the augmentation set) but there is no
    sample to warp, so its plan entry is 0 by construction -- not by the
    accident of a division guard.  ``C_bar`` still averages over ALL
    classes, empty ones included, exactly as the paper's line 1 does.
    """
    counts = np.asarray(global_counts, np.float64)
    if counts.ndim != 1:
        raise ValueError(f"global_counts must be 1-D, got shape {counts.shape}")
    c_bar = counts.mean()
    n_aug = np.zeros(counts.shape, np.int64)
    # the augmentation set: minority classes that actually have samples --
    # an empty class contributes nothing to warp (explicit, tested)
    grow = (counts > 0) & (counts < c_bar)
    n_aug[grow] = np.rint((c_bar / counts[grow]) ** alpha).astype(np.int64)
    return n_aug


def planned_counts(global_counts: np.ndarray, alpha: float) -> np.ndarray:
    """Post-augmentation expected global counts (used by tests + EXPERIMENTS)."""
    counts = np.asarray(global_counts, np.float64)
    return counts * (1 + augmentation_plan(counts, alpha))


def online_mixture(global_counts: np.ndarray, alpha: float) -> np.ndarray:
    """Expected class distribution of ONE online draw from data with
    ``global_counts``: exactly ``planned_counts`` normalized to 1 (each draw
    picks sample ``i`` with probability proportional to ``1 + n_aug[y_i]``)."""
    planned = planned_counts(global_counts, alpha)
    return planned / max(planned.sum(), 1.0)


# --------------------------------------------------------------------------
# Client-side augmentation primitives (Alg. 2 line 11, ``Augment``)
# --------------------------------------------------------------------------

def _affine_params(key: Array, *, shift: float, rot: float, shear: float, zoom: float):
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    theta = jax.random.uniform(k1, (), minval=-rot, maxval=rot)
    sh = jax.random.uniform(k2, (), minval=-shear, maxval=shear)
    zx = 1.0 + jax.random.uniform(k3, (), minval=-zoom, maxval=zoom)
    zy = 1.0 + jax.random.uniform(k4, (), minval=-zoom, maxval=zoom)
    tx, ty = jax.random.uniform(k5, (2,), minval=-shift, maxval=shift)
    cos, sin = jnp.cos(theta), jnp.sin(theta)
    # inverse mapping: output grid -> input coords (rotation ∘ shear ∘ zoom)
    mat = jnp.array([[cos / zx, (sin + sh) / zx], [(-sin) / zy, cos / zy]])
    return mat, jnp.array([tx, ty])


@partial(jax.jit, static_argnames=("order",))
def random_affine(key: Array, image: Array, *, shift: float = 3.0, rot: float = 0.3,
                  shear: float = 0.2, zoom: float = 0.15, order: int = 1) -> Array:
    """One random shift+rotation+shear+zoom warp of an ``(H, W, C)`` image."""
    h, w, c = image.shape
    mat, trans = _affine_params(key, shift=shift, rot=rot, shear=shear, zoom=zoom)
    yy, xx = jnp.meshgrid(jnp.arange(h, dtype=jnp.float32),
                          jnp.arange(w, dtype=jnp.float32), indexing="ij")
    cy, cx = (h - 1) / 2.0, (w - 1) / 2.0
    coords = jnp.stack([yy - cy, xx - cx])                       # (2, H, W)
    src = jnp.tensordot(mat, coords, axes=1)                     # (2, H, W)
    src_y = src[0] + cy + trans[0]
    src_x = src[1] + cx + trans[1]

    def warp_channel(ch):
        return jax.scipy.ndimage.map_coordinates(ch, [src_y, src_x], order=order, mode="constant")

    return jnp.stack([warp_channel(image[..., i]) for i in range(c)], axis=-1)


def augment_batch(key: Array, images: Array, n_copies: int, **kw) -> Array:
    """``n_copies`` independent warps of each image: ``(n, H, W, C)`` ->
    ``(n * n_copies, H, W, C)``."""
    n = images.shape[0]
    keys = jax.random.split(key, n * n_copies).reshape(n_copies, n, -1)
    out = jax.vmap(lambda ks: jax.vmap(lambda k, im: random_affine(k, im, **kw))(ks, images))(keys)
    return out.reshape((n * n_copies,) + images.shape[1:])


# --------------------------------------------------------------------------
# Full client rebalance (Alg. 2 lines 8-13) -- numpy orchestration around
# jit'd warps, because ragged per-class growth is inherently dynamic-shape.
# --------------------------------------------------------------------------

def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length() if n > 1 else 1


@partial(jax.jit, static_argnames=("shift", "rot", "shear", "zoom", "order"))
def _warp_many(key: Array, images: Array, *, shift=3.0, rot=0.3, shear=0.2,
               zoom=0.15, order=1) -> Array:
    keys = jax.random.split(key, images.shape[0])
    return jax.vmap(lambda k, im: random_affine(
        k, im, shift=shift, rot=rot, shear=shear, zoom=zoom, order=order))(keys, images)


def rebalance_client(key: Array, images: np.ndarray, labels: np.ndarray,
                     n_aug_per_class: np.ndarray, **kw) -> tuple[np.ndarray, np.ndarray]:
    """Apply the server's plan to one client's local dataset.

    Returns the client's dataset with augmentations appended and shuffled
    (Alg. 2 line 13). All of the client's augmentations run as ONE jit'd
    warp over a power-of-two padded stack, so XLA's compile cache is hit
    across clients (a >10x init speedup vs per-class calls).
    """
    images = np.asarray(images)
    labels = np.asarray(labels)
    rng = np.random.default_rng(int(jax.random.randint(key, (), 0, 2**31 - 1)))
    n_aug = np.asarray(n_aug_per_class)[labels]          # copies per sample
    reps = np.repeat(np.arange(labels.shape[0]), n_aug)  # source index per augmentation
    if reps.size == 0:
        perm = rng.permutation(images.shape[0])
        return images[perm], labels[perm]
    total_pad = _next_pow2(reps.size)
    reps_pad = np.concatenate([reps, rng.choice(reps, total_pad - reps.size)]) \
        if total_pad != reps.size else reps
    aug = np.asarray(_warp_many(key, jnp.asarray(images[reps_pad]), **kw))[:reps.size]
    out_x = np.concatenate([images, aug])
    out_y = np.concatenate([labels, labels[reps]])
    perm = rng.permutation(out_x.shape[0])
    return out_x[perm], out_y[perm]


# --------------------------------------------------------------------------
# Online (in-round) augmentation -- the device-resident Alg. 2 pipeline.
# Everything below is jit-native with static shapes: it runs INSIDE the
# engine's compiled round program (core/engine.py), once per mediator slot
# per round, so reschedules and rounds never re-trace.
# --------------------------------------------------------------------------

# salt folded into a mediator's round key to derive its augmentation stream
# (independent of the training stream split from the same key). The async
# engine reuses the engine's round-indexed keys for every wave, so a
# mediator's augmentation draw does not depend on which wave runs it --
# which is what keeps S=0 bitwise-identical to the synchronous engine with
# augmentation enabled.
AUG_SALT = 0x617567          # "aug"

WARP_IMPLS = ("auto", "reference", "pallas")


def warp_params(key: Array, n: int, *, shift: float = 3.0, rot: float = 0.3,
                shear: float = 0.2, zoom: float = 0.15):
    """``n`` independent random affine parameter draws: (n,2,2) mats +
    (n,2) translations, the batched form of ``_affine_params``."""
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: _affine_params(
        k, shift=shift, rot=rot, shear=shear, zoom=zoom))(keys)


def affine_warp(images: Array, mats: Array, trans: Array, *,
                impl: str = "auto", order: int = 1) -> Array:
    """Warp every image of ``(B, H, W, C)`` by its own inverse affine
    ``mats (B, 2, 2)`` / ``trans (B, 2)``.

    ``impl`` picks the resampler: ``"reference"`` is the vectorized
    ``map_coordinates`` oracle (``kernels/ref.py``), ``"pallas"`` the fused
    one-launch bilinear-warp kernel (``kernels/affine_warp.py``,
    interpret-mode off-TPU), ``"auto"`` resolves to the kernel on TPU and
    the reference elsewhere (interpret-mode Pallas in a hot CPU round loop
    would be strictly slower than XLA's fused gather). Both warp each
    image on its own, so an image's result does not depend on the batch
    it is warped in.
    """
    from repro.kernels import ref as kref
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "reference"
    if impl == "pallas":
        if order != 1:
            raise ValueError("the pallas warp kernel is bilinear (order=1)")
        from repro.kernels import ops as kops
        return kops.affine_warp(images, mats, trans)
    if impl != "reference":
        raise ValueError(f"unknown warp impl {impl!r}; expected one of "
                         f"{WARP_IMPLS}")
    return kref.affine_warp(images, mats, trans, order=order)


def warp_batch(key: Array, images: Array, *, impl: str = "auto",
               order: int = 1, **kw) -> Array:
    """One random affine warp of every image in ``(B, H, W, C)``, fused
    (``affine_warp`` over ``warp_params(key, B)``)."""
    mats, trans = warp_params(key, images.shape[0], **kw)
    return affine_warp(images, mats, trans, impl=impl, order=order)


#: smallest capacity of the compacted warp; each rung above is 4x the last
LADDER_BASE = 1024


def warp_ladder(n: int) -> tuple[int, ...]:
    """The capacities ``compact_warp`` chooses among for ``n`` slots: the
    powers of 4 from ``LADDER_BASE`` below ``n``, then ``n`` itself, so no
    round can overflow the top rung."""
    rungs = []
    cap = LADDER_BASE
    while cap < n:
        rungs.append(cap)
        cap *= 4
    return (*rungs, n)


def compact_warp(images: Array, mats: Array, trans: Array, flags: Array, *,
                 impl: str = "auto", order: int = 1
                 ) -> tuple[Array, Array, Array]:
    """Warp only the flagged images of ``(N, H, W, C)``; the rest pass
    through unchanged.

    The flags are counted on the device, and ``lax.switch`` picks the
    smallest rung of ``warp_ladder(N)`` that holds them. A rung below
    ``N`` gathers the first ``cap`` flagged images with their
    ``mats (N, 2, 2)`` and ``trans (N, 2)``, warps the ``cap`` of them in
    one ``affine_warp`` call, and scatters the results back; entries past
    the count point outside the array and drop. The top rung warps all
    ``N`` and selects the flagged. Each flagged image is warped exactly as
    a warp of all ``N`` would warp it, at every rung.

    Returns ``(out, slots, capacity)``: the images, the number of flagged
    slots, and the rung that warped them (int32 device scalars).
    """
    n = images.shape[0]
    rungs = warp_ladder(n)
    flags = flags.reshape(n)
    slots = jnp.sum(flags, dtype=jnp.int32)
    rank = jnp.cumsum(flags, dtype=jnp.int32)    # 1-based rank when flagged

    def at_capacity(cap):
        if cap == n:
            # the top rung: warp every slot, keep the flagged ones
            def run_all(images):
                warped = affine_warp(images, mats, trans, impl=impl,
                                     order=order)
                sel = flags.reshape((n,) + (1,) * (images.ndim - 1))
                return jnp.where(sel, warped, images)
            return run_all

        def run(images):
            j = jnp.arange(1, cap + 1, dtype=jnp.int32)
            # the j-th flagged slot; n once j passes the count
            src = jnp.searchsorted(rank, j, side="left").astype(jnp.int32)
            take = jnp.minimum(src, n - 1)
            warped = affine_warp(images[take], mats[take], trans[take],
                                 impl=impl, order=order)
            # past the count: distinct out-of-range rows, dropped
            dst = jnp.where(j <= slots, src, n + j)
            return images.at[dst].set(warped, mode="drop",
                                      indices_are_sorted=True,
                                      unique_indices=True)
        return run

    branch = jnp.sum(slots > jnp.asarray(rungs[:-1], jnp.int32),
                     dtype=jnp.int32)
    out = jax.lax.switch(branch, [at_capacity(c) for c in rungs], images)
    return out, slots, jnp.asarray(rungs, jnp.int32)[branch]


class OnlineDraw(NamedTuple):
    """One client's Alg. 2 draw (``online_draw``): what each slot trains
    on before the warp, and the warp it gets if ``is_aug``."""
    x: Array            # (pad, H, W, C) drawn source images
    y: Array            # (pad,) their labels
    is_aug: Array       # (pad,) bool: the draw is a warped copy
    mats: Array         # (pad, 2, 2) per-slot inverse affine
    trans: Array        # (pad, 2) per-slot translation


def online_draw(key: Array, x: Array, y: Array, mask: Array, plan: Array,
                **kw) -> OnlineDraw:
    """Fixed-shape class-conditional resample of one padded client batch.

    ``x (pad, H, W, C)`` / ``y (pad,)`` / ``mask (pad,)`` are the client's
    packed slot tensors; ``plan (num_classes,)`` is the server's broadcast
    ``n_aug`` array.  Every output slot draws a source sample from the
    seeded categorical with weights ``mask * (1 + plan[y])`` -- sample
    ``i``'s post-augmentation multiplicity -- and the draw is a warped copy
    with probability ``plan[y] / (1 + plan[y])`` (of the ``1 + n_aug``
    copies of a class-``y`` sample, ``n_aug`` are augmentations).  Hence

    * expected class mixture of the draws == ``planned_counts`` normalized
      (``online_mixture``), exactly;
    * expected warped fraction within class ``y`` == ``n_aug_y/(1+n_aug_y)``,
      matching Alg. 2's originals-plus-copies composition;
    * shapes (and the round trace) are static; the caller's mask keeps its
      meaning (an all-dummy slot stays an exact no-op: all weights 0 keeps
      the loss mask 0 regardless of content).

    The warp itself is left to ``compact_warp``, so a caller can warp the
    slots of many clients at once, and only those it trains on.
    """
    plan_f = jnp.asarray(plan).astype(jnp.float32)
    mult = 1.0 + plan_f[y]                         # per-sample multiplicity
    w = mask * mult
    k_sel, k_flag, k_warp = jax.random.split(key, 3)
    logits = jnp.where(w > 0, jnp.log(jnp.maximum(w, 1e-30)), -jnp.inf)
    idx = jax.random.categorical(k_sel, logits, shape=(y.shape[0],))
    # all-padding slot row (dummy client): every logit is -inf and the
    # categorical degenerates -- pin the gather to row 0 (masked anyway)
    idx = jnp.where(jnp.any(w > 0), idx, 0)
    sx = jnp.take(x, idx, axis=0)
    sy = jnp.take(y, idx, axis=0)
    s_mult = 1.0 + plan_f[sy]
    p_aug = (s_mult - 1.0) / s_mult                # n_aug / (1 + n_aug)
    is_aug = jax.random.uniform(k_flag, p_aug.shape) < p_aug
    mats, trans = warp_params(k_warp, y.shape[0], **kw)
    return OnlineDraw(sx, sy, is_aug, mats, trans)


def online_augment_batch(key: Array, x: Array, y: Array, mask: Array,
                         plan: Array, *, impl: str = "auto", order: int = 1,
                         **kw) -> tuple[Array, Array]:
    """One client's draw (``online_draw``) with every drawn-as-augmented
    slot warped, masked-in or not. Returns ``(x_drawn, y_drawn)``; the
    mask is unchanged by construction. The engine instead warps only the
    masked-in augmented slots of the whole round (``compact_warp``)."""
    d = online_draw(key, x, y, mask, plan, **kw)
    out, _, _ = compact_warp(d.x, d.mats, d.trans, d.is_aug, impl=impl,
                             order=order)
    return out, d.y


AUG_MODES = (None, "online", "materialized")


class AugPhase(NamedTuple):
    """Resolved Alg. 2 initialization phase (``resolve_aug_mode``)."""
    data: object                    # FederatedDataset (rebuilt if materialized)
    plan: np.ndarray | None         # the server's n_aug array (None = NoAug)
    engine_plan: np.ndarray | None  # plan to hand the round engine (online)
    extra_storage_frac: float       # realized (materialized mode only)
    planned_extra_frac: float       # what materializing would cost
    mode: str | None                # effective mode after the alpha gate


def resolve_aug_mode(data, alpha: float | None, aug_mode: str | None,
                     seed: int) -> AugPhase:
    """Shared trainer-side resolution of the rebalancing phase.

    Both ``AstraeaTrainer`` and ``FedAvgTrainer`` route through here so the
    mode semantics can never drift between them: ``alpha=None`` disables
    augmentation regardless of ``aug_mode``; ``"materialized"`` rebuilds the
    federation up front (keyword ``dataclasses.replace``, never positional);
    ``"online"`` returns the plan for the engine's in-round pipeline.  An
    all-zero online plan (already-balanced federation, or alpha small
    enough that every count rounds to 0 copies) resolves to NO engine plan:
    there is nothing to augment, so the round program must stay the exact
    no-aug executable rather than pay a resample+warp that selects nothing.
    """
    if aug_mode not in AUG_MODES:
        raise ValueError(f"unknown aug_mode {aug_mode!r}; "
                         f"expected one of {AUG_MODES}")
    mode = aug_mode if alpha is not None else None
    if mode is None:
        return AugPhase(data, None, None, 0.0, 0.0, None)
    counts = data.client_counts().sum(axis=0)
    planned = planned_counts(counts, alpha)
    planned_frac = float(planned.sum() / max(counts.sum(), 1.0) - 1.0)
    if mode == "materialized":
        cx, cy, plan, extra = rebalance_federation(
            jax.random.fold_in(jax.random.PRNGKey(seed), 17),
            data.client_images, data.client_labels, data.num_classes, alpha)
        data = dataclasses.replace(data, client_images=cx, client_labels=cy)
        return AugPhase(data, plan, None, extra, planned_frac, mode)
    plan = augmentation_plan(counts, alpha)
    engine_plan = plan if plan.any() else None
    return AugPhase(data, plan, engine_plan, 0.0, planned_frac, mode)


def resolve_engine_plan(phase: AugPhase, adaptive_plan: bool,
                        alpha: float | None
                        ) -> tuple[np.ndarray | None, float | None]:
    """Shared trainer-side adaptive-plan resolution (both trainers route
    through here, like ``resolve_aug_mode``, so the semantics can never
    drift): returns ``(engine_plan, adaptive_aug_alpha)`` for the engine.

    Adaptive mode requires the online pipeline and installs the in-round
    hook even when the *initial* plan is all-zero -- a later cohort may
    drift into needing one -- whereas the static path keeps the zero-plan
    fast path (no hook, exact no-aug executable).
    """
    if not adaptive_plan:
        return phase.engine_plan, None
    if phase.mode != "online":
        raise ValueError("adaptive_plan requires aug_mode='online' with "
                         "alpha set (the plan must live inside the round "
                         "program to be refreshed)")
    return phase.plan, alpha


def rebalance_federation(key: Array, client_images: list[np.ndarray],
                         client_labels: list[np.ndarray], num_classes: int,
                         alpha: float, **kw):
    """End-to-end Alg. 2 over a federation.

    Returns (new_client_images, new_client_labels, plan, extra_storage_frac).
    """
    counts = np.zeros(num_classes)
    for y in client_labels:
        counts += np.bincount(y, minlength=num_classes)
    plan = augmentation_plan(counts, alpha)
    out_x, out_y = [], []
    for i, (x, y) in enumerate(zip(client_images, client_labels)):
        cx, cy = rebalance_client(jax.random.fold_in(key, i), x, y, plan, **kw)
        out_x.append(cx)
        out_y.append(cy)
    before = sum(x.shape[0] for x in client_images)
    after = sum(x.shape[0] for x in out_x)
    return out_x, out_y, plan, (after - before) / max(before, 1)
