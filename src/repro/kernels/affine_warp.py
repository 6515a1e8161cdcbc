"""Pallas TPU kernel: fused batched bilinear affine warp (Alg. 2 ``Augment``).

One launch warps a whole ``(B, H, W, C)`` batch -- the client-side
augmentation primitive of the online rebalancing pipeline.  The old path
stacked one ``map_coordinates`` call per channel per image; here each grid
step warps ALL channels of one image for a ``BLOCK_P``-pixel slice of the
output in a single MXU contraction.

Layout: the image enters as ``(C, HW)`` -- pixels on the 128-wide lane axis,
so a 28x28x1 image is 784 dense lanes, not 28 rows of one lane each -- and
the inverse-mapped source coordinates of every output pixel enter as a
``(2, HW)`` lane-dense plane, computed outside the kernel by
``kernels/ref.py::source_coords``, the coordinate map of the
``map_coordinates`` oracle, so both resample at bit-identical coordinates.

Per grid step: split the block's source coordinates into the four bilinear
corners, build the sparse ``(HW, BLOCK_P)`` gather matrix from one iota
offset plane (corner ``(dy, dx)`` sits at offset ``dy * W + dx`` from the
top-left corner; out-of-bounds corners get weight 0 == ``mode="constant"``
zero fill), and contract the ``(C, HW)`` image against it.  A gather
becomes a matmul -- the standard trick for resamplers on a systolic array,
since Mosaic has no efficient arbitrary dynamic gather.

VMEM: the gather plane is ``HW x BLOCK_P`` fp32 -- 0.8 MB at 28x28 and
1 MB at 32x32 for ``BLOCK_P = 256``, a few such planes live at once.
Sized for the paper's mobile-vision inputs, not megapixel frames.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import source_coords

BLOCK_P = 256                   # output pixels (lanes) per grid step


def cost_estimate(b: int, h: int, w: int, c: int,
                  img_bytes: int = 4) -> pl.CostEstimate:
    """Analytic cost of one warp launch (also the roofline terms).

    Per image the gather-matrix build touches the (HW, HW) offset plane
    (subtract + four compare/selects ~ 9 ops per element) and the
    contraction is a (C, HW) x (HW, HW) matmul; corner math is O(HW)
    noise. HBM traffic is one image read + one image write plus the
    (2, HW) source-coordinate plane -- the gather matrix never leaves
    VMEM, which is the whole point of the fusion.
    """
    hw = h * w
    return pl.CostEstimate(
        flops=b * (2 * hw * hw * c + 9 * hw * hw),
        transcendentals=0,
        bytes_accessed=b * (2 * hw * c * img_bytes + 2 * hw * 4),
    )


def _kernel(coord_ref, img_ref, o_ref, *, h, w):
    hw = h * w
    sy = coord_ref[0, 0:1, :]                             # (1, BLOCK_P)
    sx = coord_ref[0, 1:2, :]
    y0, x0 = jnp.floor(sy), jnp.floor(sx)
    fy, fx = sy - y0, sx - x0
    # top-left corner's flat source index; the other three sit at fixed
    # offsets from it, so one (HW, BLOCK_P) offset plane serves all four
    base = (y0 * w + x0).astype(jnp.int32)
    q = jax.lax.broadcasted_iota(jnp.int32, (hw, sy.shape[1]), 0)
    off = q - base
    gather = jnp.zeros(off.shape, jnp.float32)
    for oy, ox in ((0, 0), (0, 1), (1, 0), (1, 1)):
        yy, xx = y0 + oy, x0 + ox
        wgt = (fy if oy else 1.0 - fy) * (fx if ox else 1.0 - fx)
        valid = (yy >= 0) & (yy <= h - 1) & (xx >= 0) & (xx <= w - 1)
        wgt = jnp.where(valid, wgt, 0.0)
        # distinct corners have distinct offsets (W > 1), so each plane
        # entry is claimed by at most one corner
        gather = jnp.where(off == oy * w + ox, wgt, gather)
    img = img_ref[0].astype(jnp.float32)                  # (C, HW)
    out = jnp.dot(img, gather, preferred_element_type=jnp.float32,
                  precision=jax.lax.Precision.HIGHEST)
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def affine_warp(images: jax.Array, mats: jax.Array, trans: jax.Array, *,
                interpret: bool = True) -> jax.Array:
    """images (B, H, W, C); mats (B, 2, 2); trans (B, 2) -> (B, H, W, C)."""
    h, w = images.shape[1:3]
    return resample(images, source_coords(h, w, mats, trans),
                    interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def resample(images: jax.Array, coords: jax.Array, *,
             interpret: bool = True) -> jax.Array:
    """Bilinear, zero-filled resample of images (B, H, W, C) at source
    coordinates (B, 2, H, W) -> (B, H, W, C): the kernel itself."""
    b, h, w, c = images.shape
    hw = h * w
    bp = min(BLOCK_P, hw)
    hw_pad = -(-hw // bp) * bp
    coords = coords.astype(jnp.float32).reshape(b, 2, hw)
    if hw_pad != hw:
        coords = jnp.pad(coords, ((0, 0), (0, 0), (0, hw_pad - hw)))
    planar = jnp.transpose(images.reshape(b, hw, c), (0, 2, 1))   # (B, C, HW)
    out = pl.pallas_call(
        functools.partial(_kernel, h=h, w=w),
        grid=(b, hw_pad // bp),
        in_specs=[
            pl.BlockSpec((1, 2, bp), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, c, hw), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, c, bp), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b, c, hw_pad), images.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=cost_estimate(b, h, w, c, images.dtype.itemsize),
        interpret=interpret,
        name="affine_warp",
    )(coords, planar)
    return jnp.transpose(out[:, :, :hw], (0, 2, 1)).reshape(b, h, w, c)
