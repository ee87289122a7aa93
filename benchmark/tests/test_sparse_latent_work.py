"""The picking configuration's own pieces of the yardstick, static (they
also run in tier-1, ``tests/test_benchmark_yardstick.py``): the counts of
``lib/sparse_latent_work`` by hand, the reference's side of the contract,
the cell's entries to the letter of its issue, and the four readers on a
run that has nothing for them."""

import importlib.util
import json
import os
from types import SimpleNamespace

from benchmark.lib import latent_attn_work, model, sparse_latent_work, traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GLM_CONFIG = "glm-5.2-l5-e16-serve"
GLM_CELL = "glm-5.2-l5-e16-serve-longdoc-closed16"
GLM_READERS = ("attn_index_ms_per_step", "index_score_roofline",
               "sparse_latent_attn_roofline", "kv_selected_share")


def _glm_widths():
    return SimpleNamespace(layer_kinds=(2,) * 5,
                           layer_indexer=(1, 0, 0, 0, 1), num_heads=64,
                           kv_lora_rank=512, qk_rope_head_dim=64,
                           index_heads=32, index_head_dim=128,
                           index_topk=2048)


def test_index_scoring_work_by_hand():
    cfg = _glm_widths()
    assert sparse_latent_work.indexer_layers(cfg) == 2
    # a pair: 32 heads x 128 multiply-adds, 2 FLOPs each = 8,192
    assert sparse_latent_work.score_flops(cfg, 1) == 2 * 32 * 128 == 8192
    assert sparse_latent_work.score_flops(cfg, 1_000_000) == 8.192e9
    # 1,000 cached tokens: one key of 128 bf16 values in each of 2 owners
    assert sparse_latent_work.score_bytes(cfg, 1000) == 2 * 1000 * 128 * 2
    # compute-bound on a v5e once a key is scored by more than 8 queries:
    # 8,192 FLOPs a pair against 256 B a key
    assert sparse_latent_work.score_flops(cfg, 8) / 256 > 197e12 / 819e9


def test_picked_read_work_by_hand_and_never_more_rows_than_are_held():
    cfg = _glm_widths()
    # rows of 500, 2,048, 2,049 and 20,000 cached tokens: 5 layers x
    # min(context, 2,048)
    contexts = [500, 2048, 2049, 20000]
    assert sparse_latent_work.selected_rows(cfg, contexts) == \
        5 * (500 + 2048 + 2048 + 2048)
    for c in range(0, 5000, 37):
        assert sparse_latent_work.selected_rows(cfg, [c]) <= \
            latent_attn_work.latent_layers(cfg) * c
    # a picked row: 576 bf16 values at the TRUE width (the pool holds 640)
    assert sparse_latent_work.picked_bytes(cfg, 1000) == 1000 * 576 * 2
    # a (query, picked key) pair in 5 layers: 64 heads x (576 + 512) x 2
    assert sparse_latent_work.picked_flops(cfg, 1) == \
        5 * 2 * 64 * (576 + 512) == 696_320
    # the dense read of the same rows costs what latent_attn_work says
    assert sparse_latent_work.picked_flops(cfg, 1000) == \
        latent_attn_work.decode_flops(cfg, 1000)
    assert sparse_latent_work.picked_bytes(cfg, 5 * 1000) == \
        latent_attn_work.decode_bytes(cfg, 1000)
    plain = SimpleNamespace(layer_kinds=(2,) * 5, layer_indexer=None)
    assert sparse_latent_work.indexer_layers(plain) == 0


def test_the_glm_reference_keeps_the_contract():
    conf = model.load_config(GLM_CONFIG)
    ref = model.load_reference(conf)
    assert ref.__name__.endswith("glm_moe_dsa_decoder")
    w = ref.Widths.from_hf(model.published_keys(conf))
    assert hash(w) == hash(ref.Widths.from_hf(model.published_keys(conf)))
    assert (w.hidden, w.heads, w.q_lora, w.kv_lora, w.nope, w.rope,
            w.v_head) == (6144, 64, 2048, 512, 192, 64, 256)
    assert (w.index_heads, w.index_dim, w.index_topk, w.owners) == \
        (32, 128, 2048, (1, 0, 0, 0, 1))
    assert w.sparse == (0, 1, 1, 1, 1) and w.shared_ffn == 2048
    assert (w.router_experts, w.first_expert, w.held_experts, w.per_token,
            w.groups, w.groups_kept, w.routed_scale) == \
        (256, 0, 16, 8, 1, 1, 2.5)
    assert w.yarn is None and w.theta == 8e6 and w.eps == 1e-5
    assert ref.latent.score_scale(w) == 256 ** -0.5
    attn = 6144 * 2048 + 2048 * 64 * 256 + 6144 * 576 \
        + 512 * 64 * (192 + 256) + 64 * 256 * 6144
    assert attn == 165_019_648                  # the issue's 165.0M
    indexer = 2048 * 32 * 128 + 6144 * 128 + 6144 * 32
    assert indexer == 9_371_648                 # ... and its 9.37M
    sparse = 6144 * 256 + 3 * 6144 * 2048 + 3 * 6144 * 2048 // 2
    assert ref.matmul_params_per_token(w) == 5 * attn + 2 * indexer \
        + 3 * 6144 * 12288 + 4 * sparse + 6144 * 19360
    with open(ref.__file__) as fh:
        text = fh.read()
    assert "deepspeed_tpu" not in text.split('"""', 2)[2]
    rehearsed = ref.Widths.from_hf(model.published_keys(conf, True))
    assert rehearsed.owners == (1, 0, 1) and rehearsed.index_topk == 16


def test_the_glm_cell_is_the_issues_to_the_letter():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cell = next(w for w in bench["workloads"] if w["name"] == GLM_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (GLM_CONFIG, "longdoc-closed16", 1)
    mix = traffic.load_mix("longdoc-closed16")
    assert mix["arrival"] == {"process": "closed", "clients": 16}
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 10240,
                                    "sigma": 0.4, "min": 4096, "max": 20480}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 192,
                                    "sigma": 0.5, "min": 48, "max": 512}
    assert (mix["max_total_tokens"], mix["cycle_seed"]) == (20992, 52)
    conf = model.load_config(GLM_CONFIG)
    engine = conf["engine"]
    assert (engine["max_sequences"], engine["num_blocks"],
            engine["block_size"], engine["max_seq_len"],
            engine["max_batch_tokens"], engine["prefill_chunk"]) == \
        (16, 2624, 128, 20992, 2048, 128)
    # 16 sessions at the cap are the arena: no request ends kv_exhausted
    assert engine["max_sequences"] * mix["max_total_tokens"] == \
        engine["num_blocks"] * engine["block_size"]
    sizes = traffic.size_cycle(mix)
    assert all(4096 <= p <= 20480 and p + o <= 20992 for p, o in sizes)
    # every prompt is past the 2,048 keys a query picks: the cell has no
    # request the dense latent path serves whole
    assert min(p for p, _ in sizes) > 2 * conf["index_topk"] - 1
    listed = {m["name"] for m in bench["per_layer"]
              if GLM_CELL in m.get("workloads", ())}
    assert set(GLM_READERS) <= listed
    assert "latent_attn_decode_roofline" not in listed and \
        "expert_matmul_roofline" not in listed
    moved = {m["name"] for m in bench["end_to_end"]
             if GLM_CELL in m.get("workloads", (GLM_CELL,))}
    assert moved == {"serve_tokens_per_s", "setup_s"}


def _glm_reader(name):
    path = os.path.join(REPO, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("glm_reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_picking_readers_read_nothing_from_an_empty_run():
    """The parent of the PR that added them has no such scope, span
    argument or counter: each reader returns None and does not raise, with
    a model that picks and with one that does not."""
    for model_obj in (_glm_widths(), SimpleNamespace(layer_kinds=(2,) * 5,
                                                     layer_indexer=None),
                      None):
        run = SimpleNamespace(
            facts={"model": model_obj, "steps": [], "spans": [],
                   "traced_step_range": None},
            trace=None, peaks={"bf16_flops_per_s": 197e12,
                               "hbm_bytes_per_s": 819e9},
            span_name="benchmark/serve_step",
            program_spans=lambda name: [], flops=None, stats=None)
        for name in GLM_READERS:
            if name == "kv_selected_share":
                continue        # counters are the process's: below
            assert _glm_reader(name).read(run) is None, name
    run.facts["model"] = SimpleNamespace(layer_kinds=(2,) * 5,
                                         layer_indexer=None)
    assert _glm_reader("kv_selected_share").read(run) is None
