"""What the expert matmuls of a DECODE step must read, from shapes: the
numerator of ``expert_matmul_roofline``. Kept with the benchmark, beside
``flops.py`` (whose counts assume one dense GLU a layer).

A decode step of ``rows`` tokens routes each to ``per_token`` of the
router's ``router_experts``; this chip holds ``held`` of them. An expert no
token picked need not be read. The host cannot see the routing (it stays on
the device), so the experts hit are the EXPECTED number under the routing
the seeded random weights give, every expert equally likely: an expert is
missed by one token with probability ``1 - per_token / router_experts``, by
all with that to the power of ``rows``. Per sparse layer the step must
read ``hit x 3 x hidden x expert_ffn`` weight elements, and move each held
assignment's row in and out (``rows x per_token x held / router_experts``
assignments of ``hidden`` elements, twice). At decode shapes the weights
are all of it but a thousandth.

``cfg`` is anything with the DecoderConfig's attributes ``hidden_size,
intermediate_size, num_experts, experts_held, num_experts_per_tok,
layer_sparse``."""


def held_and_width(cfg):
    held = cfg.experts_held[1] if cfg.experts_held else cfg.num_experts
    return int(held), int(cfg.num_experts)


def expected_experts_hit(cfg, rows: int) -> float:
    """Of the experts held here, how many ``rows`` tokens pick at least
    once, in one layer, in expectation."""
    held, width = held_and_width(cfg)
    miss = 1.0 - cfg.num_experts_per_tok / width
    return held * (1.0 - miss ** int(rows))


def decode_step_bytes(cfg, rows: int, itemsize: int = 2) -> float:
    """Bytes the expert matmuls of ONE decode step of ``rows`` tokens must
    read and write, over all sparse layers."""
    held, width = held_and_width(cfg)
    d, f = int(cfg.hidden_size), int(cfg.intermediate_size)
    weights = expected_experts_hit(cfg, rows) * 3 * d * f
    assignments = rows * cfg.num_experts_per_tok * held / width
    per_layer = (weights + 2 * assignments * d) * itemsize
    return per_layer * sum(1 for s in cfg.layer_sparse if s)
