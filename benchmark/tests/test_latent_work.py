"""The latent configuration's own pieces of the yardstick: the counts of
``lib/latent_attn_work`` by hand, the reference's side of the contract, and
the three readers on a run that has nothing for them."""

import os
import re
from types import SimpleNamespace

from benchmark.lib import latent_attn_work, model

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _published_widths():
    return SimpleNamespace(layer_kinds=(2,) * 5, num_heads=64,
                           kv_lora_rank=512, qk_rope_head_dim=64)


def test_latent_decode_work_by_hand():
    cfg = _published_widths()
    assert latent_attn_work.latent_layers(cfg) == 5
    assert latent_attn_work.row_values(cfg) == 576
    # 1,000 cached rows: each read once a layer, 576 bf16 values
    assert latent_attn_work.decode_bytes(cfg, 1000) == 5 * 1000 * 576 * 2
    # ... scored by 64 heads over 576 values and summed over 512, 2 FLOPs
    assert latent_attn_work.decode_flops(cfg, 1000) == \
        5 * 1000 * 2 * 64 * (576 + 512) == 696_320_000
    # memory-bound at these widths on a v5e: 139 FLOPs a byte of row
    # against 240 of the chip (197e12 / 819e9)
    intensity = latent_attn_work.decode_flops(cfg, 1) / \
        latent_attn_work.decode_bytes(cfg, 1)
    assert 120 < intensity < 197e12 / 819e9
    mixed = SimpleNamespace(layer_kinds=(0, 1, 1), num_heads=4,
                            kv_lora_rank=0, qk_rope_head_dim=0)
    assert latent_attn_work.decode_bytes(mixed, 1000) == 0.0


def test_the_latent_reference_keeps_the_contract():
    conf = model.load_config("gigachat3.1-l5-e16-serve")
    ref = model.load_reference(conf)
    assert ref.__name__.endswith("deepseek_v3_decoder")
    w = ref.Widths.from_hf(model.published_keys(conf))
    assert hash(w) == hash(ref.Widths.from_hf(model.published_keys(conf)))
    assert (w.hidden, w.heads, w.q_lora, w.kv_lora, w.nope, w.rope,
            w.v_head) == (7168, 64, 1536, 512, 128, 64, 192)
    assert w.sparse == (0, 1, 1, 1, 1) and w.shared_ffn == 2048
    assert (w.router_experts, w.first_expert, w.held_experts, w.per_token,
            w.groups, w.groups_kept, w.routed_scale) == \
        (256, 0, 16, 8, 8, 4, 2.5)
    assert w.yarn == (64.0, 4096, 32.0, 1.0, 1.0, 1.0)
    assert ref.score_scale(w) == 192 ** -0.5 * 1.4158883083359673 ** 2
    # one dense + four sparse layers, half an expert's three matrices a
    # token on this chip (8 x 16 / 256), the shared expert whole
    attn = 132_579_328
    sparse = 7168 * 256 + 3 * 7168 * 2048 + 3 * 7168 * 2048 // 2
    assert ref.matmul_params_per_token(w) == \
        5 * attn + 3 * 7168 * 18432 + 4 * sparse + 7168 * 16032
    with open(ref.__file__) as fh:
        text = fh.read()
    assert not re.search(r"^\s*(import|from)\s+deepspeed_tpu", text, re.M)
    # its routing margin is its own reading, wider than MiMo-V2's
    from benchmark.reference import mimo_v2_decoder
    assert ref.UNDECIDED_LOGIT_MARGIN == 0.16 > \
        mimo_v2_decoder.UNDECIDED_LOGIT_MARGIN


def test_the_latent_readers_give_nothing_where_there_is_nothing():
    """A program without the scopes, the counter or latent layers (the
    parent of the PR that added them): every reader returns None."""
    import importlib.util
    from benchmark.lib import stats
    from benchmark.trace import reduce
    # (``stats`` / ``reduce``: whatever spans another test left in the
    # process-wide tracer are summarised through them)
    run = SimpleNamespace(facts={}, trace=None, peaks=None,
                          span_name="benchmark/serve_step",
                          program_spans=lambda name: [], stats=stats,
                          reduce=reduce)
    for name in ("latent_attn_decode_roofline", "attn_latent_ms_per_step",
                 "moe_shared_ms_per_step"):
        path = os.path.join(REPO, "benchmark", "layer_metrics", name + ".py")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.read(run) is None, name
