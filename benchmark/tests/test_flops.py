"""The FLOPs and bytes functions against hand counts for the three
configurations the benchmark runs."""

import json
import os

import pytest

from benchmark.lib import flops, model

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# one Mistral-7B layer, by hand: q 4096x4096, k and v 4096x1024 each,
# o 4096x4096, three GLU matrices 4096x14336
LAYER = 4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 14336
HEAD = 4096 * 32000


@pytest.mark.parametrize("name,layers", [("mistral7b-l4-train", 4),
                                         ("mistral7b-l12-serve", 12),
                                         ("mistral7b-l16-train-zero3", 16)])
def test_matmul_params_and_flops_per_token(name, layers):
    cfg = model.build_model(model.load_config(name))
    assert LAYER == 218_103_808
    assert flops.matmul_params(cfg) == layers * LAYER + HEAD
    # the program's own count adds the embedding and the norm scales
    assert cfg.num_params() == layers * (LAYER + 2 * 4096) + 2 * HEAD + 4096
    # attention at sequence 4096: each token sees (4096 + 1) / 2 keys on
    # average; QK^T and PV are 2 x 2 x 4096 FLOPs a key a layer, forward;
    # three times that with the backward
    attn = 3 * layers * 4 * 4096 * (4097 / 2)
    assert flops.train_flops_per_token(cfg, 4096) == pytest.approx(
        6 * (layers * LAYER + HEAD) + attn)


def test_l4_is_the_issues_6_42_gflop_a_token():
    cfg = model.build_model(model.load_config("mistral7b-l4-train"))
    assert flops.train_flops_per_token(cfg, 4096) / 1e9 == pytest.approx(
        6.42, abs=0.01)


def test_causal_pairs_with_and_without_a_window():
    assert flops.causal_pairs(4, None) == 10
    assert flops.causal_pairs(4, 4) == 10
    # window 2 over 4 queries: 1 + 2 + 2 + 2 visible keys
    assert flops.causal_pairs(4, 2) == 7
    assert flops.causal_pairs(4096, 4096) == 4096 * 4097 // 2


def test_flash_flops_a_step():
    cfg = model.build_model(model.load_config("mistral7b-l4-train"))
    pairs = 4096 * 4097 // 2
    fwd = 4 * 4 * 32 * 128 * pairs              # layers x 4 x heads x dim
    assert flops.attention_flops_fwd(cfg, 4096) == fwd
    assert flops.flash_train_flops_per_step(cfg, 4096, 4) == 3 * fwd * 4


def test_paged_kv_bytes():
    cfg = model.build_model(model.load_config("mistral7b-l12-serve"))
    # K and V, 12 layers, 8 KV heads of 128, bf16: 49,152 bytes a token
    assert flops.paged_kv_bytes(cfg, 1) == 2 * 12 * 8 * 128 * 2
    assert flops.paged_kv_bytes(cfg, 45000) == 49152 * 45000


def test_roofline_share_takes_the_binding_bound():
    peak = flops.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    # 197 TFLOP in 2 s is half the compute roof
    assert flops.roofline_share(197e12, 1.0, 2.0, peak) == pytest.approx(50)
    # 819 GB in 4 s is a quarter of the memory roof
    assert flops.roofline_share(1.0, 819e9, 4.0, peak) == pytest.approx(25)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")


def configs():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return [c["name"] for c in json.load(fh)["configs"]]


@pytest.mark.parametrize("name", configs())
def test_the_files_keys_are_the_models_the_program_builds(name):
    from deepspeed_tpu.models.mistral import mistral_config
    import dataclasses
    conf = model.load_config(name)
    built = model.build_model(conf)             # the width check, whole
    if conf["model_type"] == "mistral":
        preset = mistral_config(
            "7b", num_layers=conf["num_hidden_layers"],
            max_seq_len=conf["max_position_embeddings"])
        assert dataclasses.asdict(built) == dataclasses.asdict(preset)
    # the architecture's own count, from the FILE's widths, is what `mfu`
    # multiplies; the dense reference answers with the dense count
    reference = model.load_reference(conf)
    widths = reference.Widths.from_hf(model.published_keys(conf))
    count = reference.matmul_params_per_token(widths)
    assert 0 < count <= built.num_active_params()
    if "reference" not in conf:
        assert count == flops.matmul_params(built)
        assert flops.train_flops_per_token(built, 4096, count) == \
            flops.train_flops_per_token(built, 4096)


def test_the_program_is_held_to_the_files_expert_keys():
    mixtral = {"model_type": "mixtral", "hidden_size": 256,
               "intermediate_size": 512, "num_attention_heads": 2,
               "num_key_value_heads": 1, "num_hidden_layers": 2,
               "vocab_size": 512, "num_local_experts": 8,
               "num_experts_per_tok": 2, "layer_types": ["full_attention"],
               "stands_for": "a test", "rehearsal": {"num_local_experts": 4}}
    # lists reach the program's reader, bookkeeping keys do not
    assert model.published_keys(mixtral)["layer_types"] == ["full_attention"]
    assert "stands_for" not in model.published_keys(mixtral)
    assert model.build_model(mixtral).num_experts == 8
    # a file's `rehearsal` block shrinks what the dense table does not know
    assert model.build_model(mixtral, rehearse=True).num_experts == 4
    # a reader that drops the experts (here: the dense family's) is refused
    with pytest.raises(ValueError, match="num_local_experts"):
        model.build_model(dict(mixtral, model_type="mistral"))
    # experts with a width of their own: the dense width is of no layer
    shared = {"model_type": "qwen2_moe", "hidden_size": 256,
              "intermediate_size": 1024, "moe_intermediate_size": 128,
              "shared_expert_intermediate_size": 512,
              "num_attention_heads": 2, "num_key_value_heads": 1,
              "num_hidden_layers": 2, "vocab_size": 512, "num_experts": 4,
              "num_experts_per_tok": 2}
    built = model.build_model(shared)
    assert (built.intermediate_size, built.shared_expert_size) == (128, 512)
