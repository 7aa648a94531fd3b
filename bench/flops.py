"""Operations the paper CNN requires, counted from its shapes.

Only the convolutions and the dense layers are counted, a multiply-add
as two operations; biases, ReLUs and pooling add under 1%. A training
example costs three forward passes (the forward, and the backward's
two products per layer: with respect to the activations and to the
weights).
"""
from __future__ import annotations

from typing import Sequence


def cnn_forward_flops(channels: Sequence[int] = (32, 32, 64, 64, 128, 128,
                                                 256, 256),
                      dense: int = 256, n_classes: int = 10,
                      image: int = 32, in_ch: int = 3) -> int:
    """One image through the 3x3 SAME convolutions (2x2 max-pool after
    every second) and the two dense layers."""
    flops, side, c_in = 0, image, in_ch
    for i, c_out in enumerate(channels):
        flops += 2 * side * side * 9 * c_in * c_out
        c_in = c_out
        if i % 2 == 1:
            side //= 2
    flat = side * side * c_in
    return flops + 2 * flat * dense + 2 * dense * n_classes


def model_forward_flops(model: dict) -> int:
    """``cnn_forward_flops`` at a configuration's ``model`` widths."""
    return cnn_forward_flops(model["channels"], model["dense"],
                             model["n_classes"], model["image"],
                             model["in_channels"])
