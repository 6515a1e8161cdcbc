"""Operation, byte and parameter counts from a configuration's layer
table, kept with the benchmark so that no change to the program can
change how its work is counted.

* Model FLOPs: 2 x the multiply-adds of every conv and dense layer at its
  output shape; a training step costs 3 x the forward (forward, and the
  backward's two products). Biases, activations, pooling and dropout do
  not count.
* The online warp's algorithmic work, whatever implements it: per image
  one read of the ``C*H*W`` image, one write of the result and one read of
  the ``2*H*W`` source coordinates, all f32; four multiply-adds (the
  bilinear taps) per output element.
"""
from __future__ import annotations

import math


def layer_walk(cfg: dict) -> list[dict]:
    """Every layer of ``cfg["layers"]`` with its input and output shapes,
    its parameter shapes and its multiply-adds per sample."""
    h, w, c = cfg["input_shape"]
    shape = (h, w, c)
    out = []
    for layer in cfg["layers"]:
        kind = layer["kind"]
        row = dict(layer, in_shape=shape, params={}, macs=0)
        if kind == "conv":
            k, s, cin = layer["k"], layer["stride"], shape[2]
            if layer["padding"] == "VALID":
                oh, ow = (shape[0] - k) // s + 1, (shape[1] - k) // s + 1
            elif layer["padding"] == "SAME":
                oh, ow = -(-shape[0] // s), -(-shape[1] // s)
            else:
                raise ValueError(f"unknown padding {layer['padding']!r}")
            cout = layer["cout"]
            shape = (oh, ow, cout)
            row["params"] = {"w": (k, k, cin, cout), "b": (cout,)}
            row["macs"] = oh * ow * cout * k * k * cin
        elif kind == "dense":
            (din,) = shape
            shape = (layer["units"],)
            row["params"] = {"w": (din, layer["units"]), "b": (layer["units"],)}
            row["macs"] = din * layer["units"]
        elif kind == "maxpool":
            win = layer["window"]
            shape = (shape[0] // win, shape[1] // win, shape[2])
        elif kind == "flatten":
            shape = (math.prod(shape),)
        elif kind not in ("relu", "dropout"):
            raise ValueError(f"unknown layer kind {kind!r}")
        row["out_shape"] = shape
        out.append(row)
    return out


def param_shapes(cfg: dict) -> dict:
    """``{layer: {"w": shape, "b": shape}}`` of the parameterised layers."""
    return {r["name"]: r["params"] for r in layer_walk(cfg) if r["params"]}


def param_count(cfg: dict) -> int:
    return sum(math.prod(s) for p in param_shapes(cfg).values()
               for s in p.values())


def forward_flops(cfg: dict) -> int:
    """Forward FLOPs of one sample: 2 x multiply-adds of conv and dense."""
    return 2 * sum(r["macs"] for r in layer_walk(cfg))


def train_flops(cfg: dict) -> int:
    """FLOPs of one sample through a training step: 3 x the forward."""
    return 3 * forward_flops(cfg)


def warp_work(h: int, w: int, c: int) -> dict:
    """Algorithmic FLOPs and HBM bytes of warping one ``(h, w, c)`` f32
    image by bilinear resampling at precomputed source coordinates."""
    hw = h * w
    return {"flops": 2 * 4 * hw * c,
            "bytes": 4 * (2 * hw * c + 2 * hw)}
