"""What a launch's gated short-convolution mixers must compute and move,
from shapes: the numerators of ``conv_mixer_roofline``. Kept with the
benchmark, beside ``flops.py``.

A gated short convolution (LFM2's; layer kind 5) on hidden size ``D`` with
``K`` taps is, for every token slot the launch's token-wise form holds, the
input projection ``D x 3D`` and the output projection ``D x D`` — 2 FLOPs a
multiply-add — and, elementwise, the two gates and the ``K`` taps over ``D``
channels. Its bytes, whatever implements it: the two projections and the
taps read ONCE a layer and launch; a slot's normed input read and its
output written (the compute dtype); and, for every row the launch advances,
the ``K − 1`` carried rows of ``D`` read and written. Intermediates between
the two projections are not counted: a fused mixer would keep them on the
chip, so the share reads low for a form that writes them, never high.

``cfg`` is anything with the DecoderConfig's attributes ``layer_kinds,
hidden_size, ssm_conv_kernel``."""


def conv_layers(cfg) -> int:
    """Short-convolution layers of the stack (a program that has no such
    kind, or no typed stack at all: 0)."""
    return sum(1 for kind in getattr(cfg, "layer_kinds", None) or ()
               if kind == 5)


def mixer_flops(cfg, token_slots: int) -> float:
    """FLOPs of the mixers of launches that hold ``token_slots`` slots IN
    ALL, over all short-convolution layers: the projections' ``2 x 4D²`` a
    slot, the taps' ``2KD`` and the two gates' ``2D``."""
    d, k = int(cfg.hidden_size), int(cfg.ssm_conv_kernel)
    return float(conv_layers(cfg) * int(token_slots) *
                 (8 * d * d + 2 * k * d + 2 * d))


def mixer_bytes(cfg, launches: int, token_slots: int, state_rows: int,
                itemsize: int = 2) -> float:
    """Bytes those launches must move, over all short-convolution layers:
    the weights once a launch, a slot's activations in and out, a row's
    tail in and out."""
    d, k = int(cfg.hidden_size), int(cfg.ssm_conv_kernel)
    weights = 4 * d * d + k * d
    return float(conv_layers(cfg) * itemsize * (
        int(launches) * weights + int(token_slots) * 2 * d +
        int(state_rows) * 2 * (k - 1) * d))
