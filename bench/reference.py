"""Plain reference of the federated rounds the benchmark times.

Written from the algorithms, in straightforward ``jax.numpy`` and numpy,
and importing nothing of the program:

* the cohort: ``c`` distinct clients a round, drawn by
  ``numpy.random.default_rng(seed).choice`` (one draw per round);
* Alg. 3: the greedy mediator schedule as a host loop over float32 scores
  -- each mediator absorbs the unassigned client that brings its merged
  label histogram closest to uniform, ties to the lowest cohort position
  (float32 is what the configuration states; in float64 near-ties of
  the KLD scores break the other way);
* Alg. 2 online: each client slot is resampled class-conditionally with
  weights ``mask * (1 + plan[y])`` and a draw of a minority class is an
  affine warp with probability ``plan[y] / (1 + plan[y])``; the warp is a
  bilinear gather with zero fill;
* local training: ``E`` epochs of masked minibatch steps over the padded
  client, clients of a mediator in sequence, for ``E_m`` mediator epochs;
  the model is walked from the configuration's layer table;
* Eq. 6 and the server fold: the size-weighted mean of the mediator deltas
  added to the weights (Astraea), or of the client weights (FedAvg).

The random streams follow the engine's documented key schedule, so that
program and reference draw the same cohorts, dropout masks, batch orders
and warps: round keys ``fold_in(PRNGKey(seed + 1), round)`` split over the
mediators, the augmentation stream ``fold_in(key, AUG_SALT)``, and the
splits of ``core/fl.py`` and ``core/mediator.py``.

In float32 every product runs at ``highest`` precision. ``dtype=bfloat16``
gives the control: the same rounds with weights, optimizer state, pixels
and arithmetic in bfloat16 (sampling decisions stay in float32).
"""
from __future__ import annotations

import contextlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import counting

AUG_SALT = 0x617567        # the augmentation stream's salt ("aug")


# ----------------------------------------------------------------------
# model from the layer table
# ----------------------------------------------------------------------

def make_init(cfg: dict):
    """Jitted ``init(key) -> params``: He-normal weights, zero biases, in
    float32, for every parameterised layer of the table -- all leaves in
    one call on the device."""
    shapes = counting.param_shapes(cfg)

    @jax.jit
    def init(key):
        params = {}
        for i, (name, p) in enumerate(shapes.items()):
            w = p["w"]
            fan_in = int(np.prod(w[:-1]))
            params[name] = {
                "w": jax.random.normal(jax.random.fold_in(key, i), w,
                                       jnp.float32) * np.sqrt(2.0 / fan_in),
                "b": jnp.zeros(p["b"], jnp.float32)}
        return params

    return init


def forward(cfg: dict, params, x, *, train: bool, key=None):
    """Logits of images ``x (n, h, w, c)``. In training the dropout masks
    draw from ``split(key, cfg["dropout_key_splits"])`` in layer order."""
    keys = jax.random.split(key, cfg["dropout_key_splits"]) if train else None
    d = 0
    for layer in cfg["layers"]:
        kind = layer["kind"]
        if kind == "conv":
            p = params[layer["name"]]
            s = layer["stride"]
            x = jax.lax.conv_general_dilated(
                x, p["w"], window_strides=(s, s), padding=layer["padding"],
                dimension_numbers=("NHWC", "HWIO", "NHWC")) + p["b"]
        elif kind == "dense":
            p = params[layer["name"]]
            x = x @ p["w"] + p["b"]
        elif kind == "relu":
            x = jax.nn.relu(x)
        elif kind == "maxpool":
            win = layer["window"]
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, win, win, 1), (1, win, win, 1),
                                      "VALID")
        elif kind == "flatten":
            x = x.reshape(x.shape[0], -1)
        elif kind == "dropout":
            if train:
                keep = 1.0 - layer["rate"]
                mask = jax.random.bernoulli(keys[d], keep, x.shape)
                x = jnp.where(mask, x / keep, 0.0).astype(x.dtype)
                d += 1
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
    return x


def masked_loss(cfg, params, x, y, mask, key):
    logits = forward(cfg, params, x, train=True, key=key)
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1e-6)


def loss_on(cfg: dict, images, labels):
    """``loss(params) -> float``: mean cross-entropy on a test set, no
    dropout, in float32 at ``highest`` precision. The test set is an
    argument of the jitted program, not a constant in it, so that one
    compiled program serves every seed."""
    x, y = jnp.asarray(images), jnp.asarray(labels)

    @jax.jit
    def loss(params, x, y):
        with jax.default_matmul_precision("highest"):
            logp = jax.nn.log_softmax(forward(cfg, params, x, train=False))
            return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))

    return lambda params: float(loss(params, x, y))


# ----------------------------------------------------------------------
# optimizers
# ----------------------------------------------------------------------

def _opt(spec: dict, dtype):
    """``(init, step)`` of the configuration's client optimizer."""
    lr = spec["learning_rate"]
    if spec["name"] == "sgd":
        def init(p):
            return jnp.zeros((), jnp.int32)

        def step(p, g, s):
            return jax.tree.map(lambda a, b: (a - lr * b).astype(dtype),
                                p, g), s + 1
        return init, step
    if spec["name"] == "adam":
        b1, b2, eps = spec["b1"], spec["b2"], spec["eps"]

        def init(p):
            z = jax.tree.map(jnp.zeros_like, p)
            return (jnp.zeros((), jnp.int32), z, z)

        def step(p, g, s):
            t, mu, nu = s
            t = t + 1
            mu = jax.tree.map(lambda m, a: b1 * m + (1 - b1) * a, mu, g)
            nu = jax.tree.map(lambda v, a: b2 * v + (1 - b2) * a * a, nu, g)
            bc1 = (1 - b1 ** t.astype(jnp.float32)).astype(dtype)
            bc2 = (1 - b2 ** t.astype(jnp.float32)).astype(dtype)
            p = jax.tree.map(
                lambda w, m, v: (w - lr * (m / bc1)
                                 / (jnp.sqrt(v / bc2) + eps)).astype(dtype),
                p, mu, nu)
            return p, (t, mu, nu)
        return init, step
    raise ValueError(f"unknown optimizer {spec['name']!r}")


def make_client_update(cfg: dict, batch: int, epochs: int, dtype):
    """Jitted ``client_update(params, x, y, mask, key) -> params``:
    ``epochs`` passes of masked minibatch steps over the padded client,
    each pass in the order of ``permutation(split(ekey, nb + 1)[0])``."""
    opt_init, opt_step = _opt(cfg["optimizer"], dtype)
    grad = jax.grad(partial(masked_loss, cfg))

    @jax.jit
    def client_update(params, x, y, mask, key):
        n = x.shape[0]
        nb = n // batch
        state = opt_init(params)
        for ekey in jax.random.split(key, epochs):
            ks = jax.random.split(ekey, nb + 1)
            perm = jax.random.permutation(ks[0], n)
            xs = x[perm].reshape((nb, batch) + x.shape[1:])
            ys = y[perm].reshape(nb, batch)
            ms = mask[perm].reshape(nb, batch)

            def body(carry, b):
                p, s = carry
                g = grad(p, b[0], b[1], b[2], b[3])
                return opt_step(p, g, s), None

            (params, state), _ = jax.lax.scan(body, (params, state),
                                              (xs, ys, ms, ks[1:]))
        return params

    return client_update


# ----------------------------------------------------------------------
# Alg. 2 (online) and Alg. 3
# ----------------------------------------------------------------------

def augmentation_plan(global_counts: np.ndarray, alpha: float) -> np.ndarray:
    """Alg. 2 lines 1-6: ``round((mean / C_i) ** alpha)`` copies per sample
    of every non-empty class below the mean, 0 elsewhere."""
    counts = np.asarray(global_counts, np.float64)
    grow = (counts > 0) & (counts < counts.mean())
    plan = np.zeros(counts.shape, np.int64)
    plan[grow] = np.rint((counts.mean() / counts[grow]) ** alpha)
    return plan


def _affine(key, shift, rot, shear, zoom):
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    theta = jax.random.uniform(k1, (), minval=-rot, maxval=rot)
    sh = jax.random.uniform(k2, (), minval=-shear, maxval=shear)
    zx = 1.0 + jax.random.uniform(k3, (), minval=-zoom, maxval=zoom)
    zy = 1.0 + jax.random.uniform(k4, (), minval=-zoom, maxval=zoom)
    t = jax.random.uniform(k5, (2,), minval=-shift, maxval=shift)
    c, s = jnp.cos(theta), jnp.sin(theta)
    return jnp.array([[c / zx, (s + sh) / zx], [-s / zy, c / zy]]), t


def bilinear_warp(images, mats, trans):
    """Inverse-mapped affine warp about the image centre, bilinear, zero
    outside the image: ``images (n, h, w, c)``, ``mats (n, 2, 2)``,
    ``trans (n, 2)``."""
    n, h, w, c = images.shape
    yy, xx = jnp.meshgrid(jnp.arange(h, dtype=jnp.float32),
                          jnp.arange(w, dtype=jnp.float32), indexing="ij")
    cy, cx = (h - 1) / 2.0, (w - 1) / 2.0
    m = mats[:, :, :, None, None]
    sy = m[:, 0, 0] * (yy - cy) + m[:, 0, 1] * (xx - cx) + cy \
        + trans[:, 0, None, None]
    sx = m[:, 1, 0] * (yy - cy) + m[:, 1, 1] * (xx - cx) + cx \
        + trans[:, 1, None, None]
    y0, x0 = jnp.floor(sy), jnp.floor(sx)
    fy, fx = sy - y0, sx - x0
    flat = images.reshape(n, h * w, c)
    out = jnp.zeros((n, h, w, c), images.dtype)
    for oy in (0, 1):
        for ox in (0, 1):
            yi, xi = y0 + oy, x0 + ox
            wgt = (fy if oy else 1.0 - fy) * (fx if ox else 1.0 - fx)
            ok = (yi >= 0) & (yi <= h - 1) & (xi >= 0) & (xi <= w - 1)
            idx = (jnp.clip(yi, 0, h - 1) * w
                   + jnp.clip(xi, 0, w - 1)).astype(jnp.int32)
            tap = jnp.take_along_axis(flat, idx.reshape(n, h * w, 1), axis=1)
            out = out + (jnp.where(ok, wgt, 0.0)[..., None].astype(images.dtype)
                         * tap.reshape(n, h, w, c))
    return out


def make_augment(warp: dict, dtype):
    """Jitted ``augment(key, x, y, mask, plan) -> (x', y')`` of one padded
    client (Alg. 2 online)."""

    @jax.jit
    def augment(key, x, y, mask, plan):
        plan_f = plan.astype(jnp.float32)
        wts = mask.astype(jnp.float32) * (1.0 + plan_f[y])
        k_sel, k_flag, k_warp = jax.random.split(key, 3)
        logits = jnp.where(wts > 0, jnp.log(jnp.maximum(wts, 1e-30)),
                           -jnp.inf)
        idx = jax.random.categorical(k_sel, logits, shape=(y.shape[0],))
        idx = jnp.where(jnp.any(wts > 0), idx, 0)
        sx, sy = x[idx], y[idx]
        s_mult = 1.0 + plan_f[sy]
        is_aug = jax.random.uniform(k_flag, s_mult.shape) < (s_mult - 1.0) / s_mult
        mats, trans = jax.vmap(lambda k: _affine(k, **warp))(
            jax.random.split(k_warp, y.shape[0]))
        warped = bilinear_warp(sx, mats.astype(dtype), trans.astype(dtype))
        return jnp.where(is_aug[:, None, None, None], warped, sx), sy

    return augment


@jax.jit
def _kld_to_uniform(merged):
    """``D_KL(normalize(merged) || U)`` per row, in float32."""
    c = merged.shape[-1]
    p = merged / jnp.maximum(jnp.sum(merged, axis=-1, keepdims=True), 1e-12)
    ratio = jnp.log(jnp.maximum(p, 1e-12)) - jnp.log(
        jnp.full((c,), 1.0 / c, jnp.float32))
    return jnp.sum(jnp.where(p > 0, p * ratio, 0.0), axis=-1)


def alg3(counts: np.ndarray, gamma: int) -> list[list[int]]:
    """Greedy KLD rescheduling over the cohort's histograms ``counts``
    (float32, the precision the configuration states): returns mediators
    as lists of cohort positions, in pick order."""
    counts = np.asarray(counts, np.float32)
    n, c = counts.shape
    taken = np.zeros(n, bool)
    med = np.zeros(c, np.float32)
    picks = []
    for s in range(n):
        kl = np.asarray(_kld_to_uniform(jnp.asarray(med[None, :] + counts)))
        kl = np.where(taken, np.inf, kl)
        k = int(np.argmin(kl))
        picks.append(k)
        taken[k] = True
        med = med + counts[k]
        if (s + 1) % gamma == 0:
            med = np.zeros(c, np.float32)
    return [picks[i:i + gamma] for i in range(0, n, gamma)]


# ----------------------------------------------------------------------
# rounds
# ----------------------------------------------------------------------

def schedules(traffic: dict, counts: np.ndarray, seed: int,
              rounds: int) -> list[list[list[int]]]:
    """Each round's mediators as lists of client ids, in schedule order."""
    rng = np.random.default_rng(seed)
    k = counts.shape[0]
    c = min(traffic["clients_per_round"], k)
    plan = _plan(traffic, counts)
    out = []
    for _ in range(rounds):
        sel = rng.choice(k, size=c, replace=False)
        if traffic["schedule"] == "kld":
            scaled = counts[sel] * (1.0 + plan.astype(np.float64))
            groups = alg3(scaled, traffic["gamma"])
            out.append([[int(sel[i]) for i in g] for g in groups])
        elif traffic["schedule"] == "random" and traffic["gamma"] == 1:
            out.append([[int(i)] for i in sel])
        else:
            raise ValueError(f"no reference for schedule "
                             f"{traffic['schedule']!r} at gamma "
                             f"{traffic['gamma']}")
    return out


def _plan(traffic: dict, counts: np.ndarray) -> np.ndarray:
    if traffic["aug_mode"] is None or traffic["alpha"] is None:
        return np.zeros(counts.shape[1], np.int64)
    if traffic["aug_mode"] != "online":
        raise ValueError(f"no reference for aug_mode {traffic['aug_mode']!r}")
    return augmentation_plan(counts.sum(axis=0), traffic["alpha"])


def padded_client(images, labels, pad: int):
    n = images.shape[0]
    x = np.zeros((pad,) + images.shape[1:], np.float32)
    y = np.zeros(pad, np.int32)
    m = np.zeros(pad, np.float32)
    x[:n], y[:n], m[:n] = images, labels, 1.0
    return x, y, m


def run_rounds(cfg: dict, traffic: dict, client_images, client_labels,
               counts: np.ndarray, params0, seed: int, rounds: int, *,
               dtype=jnp.float32, fault: str | None = None):
    """The first ``rounds`` rounds from ``params0``; returns
    ``(params after each round (numpy trees), schedules)``.

    ``fault`` plants a fault, to read what the comparison makes of it:
    ``"drop_half"`` takes Eq. 6 over the first half of the mediators only
    (the mean over them); ``"negate_row0"`` negates the first mediator's
    answer (its delta, or its client's weights) where it is produced."""
    b = traffic["batch_size"]
    pad = int(-(-int(counts.sum(axis=1).max()) // b) * b)
    plan = _plan(traffic, counts)
    plan_d = jnp.asarray(plan, jnp.int32)
    use_aug = bool(plan.any())
    update = make_client_update(cfg, b, traffic["epochs"], dtype)
    augment = make_augment(traffic["warp"], dtype) if use_aug else None
    sched = schedules(traffic, counts, seed, rounds)
    gamma = traffic["gamma"]
    delta = traffic["aggregate"] == "delta"
    prec = jax.default_matmul_precision("highest") \
        if dtype == jnp.float32 else contextlib.nullcontext()
    params = jax.tree.map(lambda a: jnp.asarray(a, dtype), params0)
    out = []
    with prec:
        for r, groups in enumerate(sched):
            base = jax.random.fold_in(jax.random.PRNGKey(seed + 1), r)
            keys = jax.random.split(base, len(groups))
            results, weights = [], []
            for g, members in enumerate(groups):
                k = keys[g]
                w, wt = params, 0.0
                slots = []
                aks = jax.random.split(jax.random.fold_in(k, AUG_SALT), gamma)
                for i, cid in enumerate(members):
                    x, y, m = padded_client(client_images[cid],
                                            client_labels[cid], pad)
                    wt += float((m * (1.0 + plan[y])).sum())
                    x = jnp.asarray(x, dtype)
                    y = jnp.asarray(y)
                    if use_aug:
                        x, y = augment(aks[i], x, y, jnp.asarray(m), plan_d)
                    slots.append((x, y, jnp.asarray(m, dtype)))
                if delta:
                    for ekey in jax.random.split(k, traffic["mediator_epochs"]):
                        ckeys = jax.random.split(ekey, gamma)
                        for i, (x, y, m) in enumerate(slots):
                            w = update(w, x, y, m, ckeys[i])
                    results.append(jax.tree.map(lambda a, b_: a - b_, w,
                                                params))
                else:
                    (x, y, m), = slots
                    results.append(update(params, x, y, m, k))
                weights.append(wt)
            if fault == "negate_row0":
                results[0] = jax.tree.map(lambda a: -a, results[0])
            wv = np.asarray(weights, np.float64)
            if fault == "drop_half":
                wv[len(wv) // 2:] = 0.0
            wn = jnp.asarray(wv / max(wv.sum(), 1e-12), dtype)
            agg = jax.tree.map(
                lambda *leaves: sum(wn[i] * a for i, a in enumerate(leaves)),
                *results)
            params = jax.tree.map(lambda p, a: p + a, params, agg) \
                if delta else agg
            out.append(jax.tree.map(lambda a: np.asarray(a, np.float32),
                                    params))
    return out, sched
