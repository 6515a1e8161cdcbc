"""The benchmark's federation generator, copied from ``repro.data``
(``data/federated.py:partition`` and ``data/synthetic.py``) with the two
sources of randomness split apart:

* the configuration's fixed ``structure_seed`` draws the client sizes
  (log-normal "instagram" law) and every client's class counts (Dirichlet
  around the global law, then a multinomial) -- so the pad, the packed
  store and the work of every round are the same at every ``--seed``;
* ``--seed`` draws the pixels: the class prototypes, each sample's affine
  distortion and noise, and the order of samples inside a client.

Pixels are made on the device by one jitted program, in fixed-size
chunks, and brought back to the host, where the program takes its
federation.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

# English letter relative frequencies (a..z), the paper's LTRF global law
_LETTER_FREQ = np.array([
    8.167, 1.492, 2.782, 4.253, 12.702, 2.228, 2.015, 6.094, 6.966, 0.153,
    0.772, 4.025, 2.406, 6.749, 7.507, 1.929, 0.095, 5.987, 6.327, 9.056,
    2.758, 0.978, 2.360, 0.150, 1.974, 0.074])


def letter_frequency_probs(num_classes: int) -> np.ndarray:
    """LTRF global law: letter frequencies sorted descending, with a Zipf
    tail past 26 classes (47-class EMNIST)."""
    freqs = np.sort(_LETTER_FREQ)[::-1]
    if num_classes > len(freqs):
        tail = freqs.min() / np.arange(2, num_classes - len(freqs) + 2)
        freqs = np.concatenate([freqs, tail])
    p = freqs[:num_classes]
    return p / p.sum()


def normal_pdf_probs(num_classes: int) -> np.ndarray:
    """Imbalanced CINIC-10: class counts follow the standard normal pdf."""
    z = np.linspace(-2.0, 2.0, num_classes)
    p = np.exp(-0.5 * z * z)
    return p / p.sum()


GLOBAL_LAWS = {
    "balanced": lambda c: np.full(c, 1.0 / c),
    "letterfreq": letter_frequency_probs,
    "normal": normal_pdf_probs,
}


def class_counts(cfg: dict) -> np.ndarray:
    """``(K, C)`` integer class counts of every client, from the
    configuration's ``structure_seed`` alone."""
    rng = np.random.default_rng(cfg["structure_seed"])
    k, c = cfg["num_clients"], cfg["num_classes"]
    gp = GLOBAL_LAWS[cfg["global_law"]](c)
    if cfg["size_law"] == "instagram":
        w = rng.lognormal(mean=0.0, sigma=1.0, size=k)
        sw = w / w.sum()
    elif cfg["size_law"] == "even":
        sw = np.full(k, 1.0 / k)
    else:
        raise ValueError(f"unknown size law {cfg['size_law']!r}")
    sizes = np.maximum(np.rint(sw * cfg["total_samples"]).astype(int), 2)
    counts = np.zeros((k, c), int)
    for i in range(k):
        if cfg["local_law"] == "random":
            q = rng.dirichlet(cfg["dirichlet_conc"] * c * gp)
        elif cfg["local_law"] == "matched":
            q = gp
        else:
            raise ValueError(f"unknown local law {cfg['local_law']!r}")
        counts[i] = rng.multinomial(sizes[i], q)
    return counts


def pack_reckoning(cfg: dict, batch_size: int,
                   counts: np.ndarray | None = None) -> dict:
    """Host reckoning of the padded client pack the replicated store holds
    on the device: every client padded to the largest, rounded up to a
    multiple of the batch, times (image + label + mask) bytes."""
    counts = class_counts(cfg) if counts is None else counts
    sizes = counts.sum(axis=1)
    h, w, ch = cfg["input_shape"]
    pad = int(-(-int(sizes.max()) // batch_size) * batch_size)
    sample_bytes = h * w * ch * 4 + 4 + 4
    return {"clients": int(len(sizes)), "samples": int(sizes.sum()),
            "median_client": float(np.median(sizes)),
            "mean_client": float(sizes.mean()),
            "largest_client": int(sizes.max()), "pad": pad,
            "pack_bytes": int(len(sizes) * pad * sample_bytes)}


def _prototypes(cfg: dict, rng: np.random.Generator) -> np.ndarray:
    """Smooth per-class prototypes: random low-frequency Fourier mixtures."""
    h, _, chans = cfg["input_shape"]
    yy, xx = np.mgrid[0:h, 0:h] / h
    protos = np.zeros((cfg["num_classes"], h, h, chans), np.float32)
    for c in range(cfg["num_classes"]):
        for ch in range(chans):
            img = np.zeros((h, h))
            for _ in range(cfg["prototype_freqs"]):
                fy, fx = rng.integers(1, 4, 2)
                py, px = rng.uniform(0, 2 * np.pi, 2)
                amp = rng.uniform(0.5, 1.0)
                img += amp * np.sin(2 * np.pi * fy * yy + py) \
                    * np.cos(2 * np.pi * fx * xx + px)
            protos[c, :, :, ch] = img / np.abs(img).max()
    return protos


def _draw(cfg: dict, protos: np.ndarray, labels: np.ndarray,
          key) -> np.ndarray:
    """One sample per label: its prototype under a random rotation and
    shift (nearest-neighbour remap), plus Gaussian pixel noise -- drawn on
    the device by one jitted program, returned to the host."""
    h, _, chans = cfg["input_shape"]
    s = cfg["pixel_distort"]
    noise = cfg["pixel_noise"]

    @jax.jit
    def draw(key, protos, labels):
        n = labels.shape[0]
        k1, k2, k3 = jax.random.split(key, 3)
        theta = jax.random.uniform(k1, (n, 1, 1), minval=-s, maxval=s)
        shift = jax.random.uniform(k2, (n, 2, 1, 1), minval=-s * h * 0.2,
                                   maxval=s * h * 0.2)
        yy, xx = jnp.meshgrid(jnp.arange(h, dtype=jnp.float32),
                              jnp.arange(h, dtype=jnp.float32),
                              indexing="ij")
        cy = (h - 1) / 2
        c, si = jnp.cos(theta), jnp.sin(theta)
        src_y = c * (yy - cy) - si * (xx - cy) + cy + shift[:, 0]
        src_x = si * (yy - cy) + c * (xx - cy) + cy + shift[:, 1]
        iy = jnp.clip(jnp.round(src_y).astype(jnp.int32), 0, h - 1)
        ix = jnp.clip(jnp.round(src_x).astype(jnp.int32), 0, h - 1)
        img = protos[labels[:, None, None], iy, ix]
        return img + noise * jax.random.normal(k3, img.shape, jnp.float32)

    # fixed-size chunks: one compile, and the gather's temporaries stay
    # small on the device whatever the federation's size
    n = labels.shape[0]
    chunk = 8192
    padded = np.zeros(-(-n // chunk) * chunk, labels.dtype)
    padded[:n] = labels
    protos_d = jnp.asarray(protos)
    out = [np.asarray(draw(jax.random.fold_in(key, i), protos_d,
                           jnp.asarray(padded[lo:lo + chunk])))
           for i, lo in enumerate(range(0, padded.shape[0], chunk))]
    return np.concatenate(out)[:n]


@dataclass
class Federation:
    """The generated federation: per-client arrays (views into one block)
    and the balanced test set."""
    client_images: list
    client_labels: list
    test_images: np.ndarray
    test_labels: np.ndarray
    counts: np.ndarray


def generate(cfg: dict, pixel_seed: int) -> Federation:
    """The configuration's federation, with pixels drawn from
    ``pixel_seed`` and structure from ``cfg["structure_seed"]``."""
    counts = class_counts(cfg)
    rng = np.random.default_rng(pixel_seed)
    protos = _prototypes(cfg, rng)
    labels = []
    for row in counts:
        y = np.repeat(np.arange(row.shape[0], dtype=np.int32), row)
        labels.append(y[rng.permutation(y.shape[0])])
    sizes = counts.sum(axis=1)
    key = jax.random.PRNGKey(pixel_seed % (2 ** 32))
    images = _draw(cfg, protos, np.concatenate(labels),
                   jax.random.fold_in(key, 0))
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    client_images = [images[bounds[i]:bounds[i + 1]]
                     for i in range(len(sizes))]
    c = cfg["num_classes"]
    ty = np.repeat(np.arange(c, dtype=np.int32), cfg["test_per_class"])
    tx = _draw(cfg, protos, ty, jax.random.fold_in(key, 1))
    return Federation(client_images, labels, tx, ty, counts)
