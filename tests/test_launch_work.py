"""``inference/launch_work``: what one launch of a serving step program
did, for every serving configuration of the benchmark — the dict the engine's
``_count_dispatch`` returned before the arithmetic moved here (PR 60), and
the ``dispatch/*`` counters it advanced. No engine, no parameters: the
configuration's ``DecoderConfig`` at rehearsal widths, a few integers and
two arrays."""
import types

import numpy as np
import pytest

from benchmark.lib import model as model_lib
from deepspeed_tpu.inference import launch_work
from deepspeed_tpu.inference.launch_work import Form

#: kind → (the program's form, lifted, rows' starts, rows' fed tokens, what
#: every stack's launch holds): five decoding rows in an 8-row program; six
#: rows of an 8-row batch that a 64-sequence engine at chunk 128 lifts to
#: its 64-row program's (512, 4) instance
LAUNCHES = {
    "decode": (Form(8, (), 8, 8, 8), False,
               (200, 127, 128, 4500, 9), (1, 1, 1, 1, 1),
               {"program": "decode", "rows": 5, "rows_bucket": 8, "chunk": 1,
                "tokens": 5, "slots": 8, "row_slots": 8, "chunk_rows": 0,
                "kv_write_slots": 8, "context_tokens": 4969,
                "context_slots": 40960}),
    "split": (Form(64, (512, 1024, 2048), 512, 4, 576), True,
              (300, 0, 256, 4200, 640, 77), (1, 100, 128, 1, 1, 37),
              {"program": "split", "rows": 6, "rows_bucket": 64,
               "chunk": 128, "tokens": 268, "slots": 512, "row_slots": 576,
               "chunk_rows": 3, "kv_write_slots": 512,
               "context_tokens": 5741, "context_slots": 6208}),
}

#: (configuration, kind) → what its stack adds, in the order the keys are
#: laid down: the engine's ``_count_dispatch`` of before PR 60 under
#: ``use_pallas``, pages of 128 and a page table 40 wide — and, since a typed
#: stack's decode program reads through the paged kernel too (PR 61), its
#: decode launch's pages by hand: the rows' own keys lie in pages 2 + 1 + 2 +
#: 36 + 1 = 42 of a full layer
ADDS = {
    ("command-a-plus-l4-e16-serve", "decode"): {
        "kv_tokens_full": 4969, "kv_tokens_window_live": 724,
        "kv_tokens_window_held": 4969, "attn_pairs_full": 4969,
        "attn_pairs_window": 724, "attn_pairs_own_full": 5,
        "attn_pairs_own_window": 5,
        # 42 pages to the rows' own keys in the full layer; 9 from the
        # window's first page (the row at 4,500: pages 33-35 of a window of
        # 256) in each of three window layers; every KV head a program
        "kv_pages_walked": 69, "kv_page_fetches": 138,
        "moe_assignments": 40, "moe_buffer_rows": 0},
    ("command-a-plus-l4-e16-serve", "split"): {
        "kv_tokens_full": 5741, "kv_tokens_window_live": 1365,
        "kv_tokens_window_held": 5741, "attn_pairs_full": 54769,
        "attn_pairs_window": 42138, "attn_pairs_own_full": 14012,
        "attn_pairs_own_window": 14012, "query_tiles": 67,
        "query_tiles_live": 67, "kv_pages_walked": 77,
        "kv_page_fetches": 154, "moe_assignments": 2144,
        "moe_buffer_rows": 1024},
    ("gigachat3.1-l5-e16-serve", "decode"): {
        "kv_tokens_latent": 4969, "moe_assignments": 80,
        "moe_buffer_rows": 0},
    ("gigachat3.1-l5-e16-serve", "split"): {
        "kv_tokens_latent": 5741, "moe_assignments": 4288,
        "moe_buffer_rows": 512},
    ("glm-5.2-l5-e16-serve", "decode"): {
        "kv_tokens_latent": 4969, "index_tokens_scored": 9938,
        "kv_tokens_selected": 222, "attn_pairs_selected": 74,
        "moe_assignments": 80, "moe_buffer_rows": 0},
    ("glm-5.2-l5-e16-serve", "split"): {
        "kv_tokens_latent": 5741, "index_tokens_scored": 109538,
        "kv_tokens_selected": 288, "attn_pairs_selected": 4168,
        "moe_assignments": 4288, "moe_buffer_rows": 512},
    ("granite-4.0-h-small-l10-e36-serve", "decode"): {
        "kv_pages_walked": 42, "kv_page_fetches": 84, "state_rows": 5,
        "state_resets": 0, "ssm_chunk_tokens": 0, "moe_assignments": 45,
        "moe_buffer_rows": 0},
    ("granite-4.0-h-small-l10-e36-serve", "split"): {
        "query_tiles": 35, "query_tiles_live": 35, "kv_pages_walked": 44,
        "kv_page_fetches": 88, "state_rows": 6, "state_resets": 1,
        "ssm_chunk_tokens": 265, "moe_assignments": 2412,
        "moe_buffer_rows": 1536},
    # experts in the file, no sparse layer at rehearsal depth
    ("jamba2-3b-l28-serve", "decode"): {
        "kv_pages_walked": 42, "kv_page_fetches": 84, "state_rows": 5,
        "state_resets": 0, "ssm_chunk_tokens": 0, "moe_buffer_rows": 0},
    ("jamba2-3b-l28-serve", "split"): {
        "query_tiles": 35, "query_tiles_live": 35, "kv_pages_walked": 44,
        "kv_page_fetches": 88, "state_rows": 6, "state_resets": 1,
        "ssm_chunk_tokens": 265, "moe_buffer_rows": 0},
    ("lfm2-24b-a2b-l40-e8-serve", "decode"): {
        "kv_pages_walked": 42, "kv_page_fetches": 84, "state_rows": 5,
        "state_resets": 0, "ssm_chunk_tokens": 0, "moe_assignments": 30,
        "moe_buffer_rows": 0},
    ("lfm2-24b-a2b-l40-e8-serve", "split"): {
        "query_tiles": 19, "query_tiles_live": 19, "kv_pages_walked": 44,
        "kv_page_fetches": 88, "state_rows": 6, "state_resets": 1,
        "ssm_chunk_tokens": 265, "moe_assignments": 1608,
        "moe_buffer_rows": 1536},
    ("mimo-v2.5-l7-e16-serve", "decode"): {
        "kv_tokens_full": 4969, "kv_tokens_window_live": 522,
        "kv_tokens_window_held": 4969, "attn_pairs_full": 4969,
        "attn_pairs_window": 522, "attn_pairs_own_full": 5,
        "attn_pairs_own_window": 5,
        # the full layer's 42; 8 in each of two window layers (window 128:
        # the row at 4,500 walks pages 34 and 35)
        "kv_pages_walked": 58, "kv_page_fetches": 116,
        "moe_assignments": 80, "moe_buffer_rows": 0},
    ("mimo-v2.5-l7-e16-serve", "split"): {
        "kv_tokens_full": 5741, "kv_tokens_window_live": 853,
        "kv_tokens_window_held": 5741, "attn_pairs_full": 54769,
        "attn_pairs_window": 25370, "attn_pairs_own_full": 14012,
        "attn_pairs_own_window": 14012, "query_tiles": 67,
        "query_tiles_live": 67, "kv_pages_walked": 58,
        "kv_page_fetches": 116, "moe_assignments": 4288,
        "moe_buffer_rows": 1024},
    ("mistral7b-l12-serve", "decode"): {
        "kv_pages_walked": 84, "kv_page_fetches": 168},
    ("mistral7b-l12-serve", "split"): {
        "query_tiles": 35, "query_tiles_live": 35, "kv_pages_walked": 88,
        "kv_page_fetches": 176},
    ("nemotron3-nano-l26-e16-serve", "decode"): {
        "kv_pages_walked": 42, "kv_page_fetches": 84, "state_rows": 5,
        "state_resets": 0, "ssm_chunk_tokens": 0, "moe_assignments": 10,
        "moe_buffer_rows": 0},
    ("nemotron3-nano-l26-e16-serve", "split"): {
        "query_tiles": 35, "query_tiles_live": 35, "kv_pages_walked": 44,
        "kv_page_fetches": 88, "state_rows": 6, "state_resets": 1,
        "ssm_chunk_tokens": 265, "moe_assignments": 536,
        "moe_buffer_rows": 512},
    # three delta-rule layers at rehearsal depth, heads of 32: the XLA form,
    # every position of the chunk group's 4 rows x 128 in each
    ("qwen3-next-80b-a3b-l12-e64-serve", "decode"): {
        "kv_pages_walked": 42, "kv_page_fetches": 84, "state_rows": 5,
        "state_resets": 0, "ssm_chunk_tokens": 0, "moe_assignments": 40,
        "moe_buffer_rows": 0},
    ("qwen3-next-80b-a3b-l12-e64-serve", "split"): {
        "query_tiles": 35, "query_tiles_live": 35, "kv_pages_walked": 44,
        "kv_page_fetches": 88, "state_rows": 6, "state_resets": 1,
        "ssm_chunk_tokens": 265, "delta_chunk_positions": 1536,
        "delta_chunk_positions_live": 1536, "moe_assignments": 2144,
        "moe_buffer_rows": 4096},
    ("xing4.0-29b-a4b-l6-serve", "decode"): {
        "kv_tokens_latent": 4969, "moe_assignments": 20,
        "moe_buffer_rows": 0, "hc_maps": 32},
    ("xing4.0-29b-a4b-l6-serve", "split"): {
        "kv_tokens_latent": 5741, "moe_assignments": 1072,
        "moe_buffer_rows": 1024, "hc_maps": 2048},
}

#: the keys of a launch's work that are span arguments and no counter, and
#: the counters whose name is not their key's
SPAN_ONLY = {"program", "rows", "rows_bucket", "chunk", "kv_tokens_full",
             "kv_tokens_latent", "attn_pairs_full", "attn_pairs_window",
             "attn_pairs_own_full", "attn_pairs_own_window",
             "attn_pairs_selected"}
RENAMED = {"slots": "token_slots", "row_slots": "attn_row_slots",
           "kv_tokens_window_live": "kv_window_live_tokens",
           "kv_tokens_window_held": "kv_window_held_tokens"}


def site(name: str) -> launch_work.Site:
    from deepspeed_tpu.inference.engine_v2 import _paged_reader
    model = model_lib.build_model(model_lib.load_config(name), rehearse=True)
    use_pallas, k_lanes = _paged_reader(
        model, types.SimpleNamespace(use_pallas=True, block_size=128))
    return launch_work.Site(model, 128, 40, use_pallas, k_lanes, 2)


def _counters():
    from deepspeed_tpu.telemetry.registry import registry
    return {n[len("dispatch/"):]: registry.counter(n).value
            for n in registry.names() if n.startswith("dispatch/")}


@pytest.mark.parametrize("name", sorted({n for n, _ in ADDS}))
def test_a_launchs_work_is_what_the_engine_counted(name, monkeypatch):
    assert len(ADDS) == 2 * 11
    at = site(name)
    # as the code has it an 8-row program at rehearsal widths gathers too
    # little for a typed stack's decode read to take the kernel
    # (``pa.decode_reads_by_kernel``): its launch counts no pages; the
    # uniform stack's always does
    form, _, starts, fed, _ = LAUNCHES["decode"]
    small = launch_work.launch_work(at, "decode", form, 1, np.asarray(starts),
                                    np.asarray(fed))
    assert (small.get("kv_pages_walked", 0) > 0) == (not at.model.typed)
    monkeypatch.setattr(launch_work.pa, "DECODE_KERNEL_BYTES", 0)
    for kind, (form, lifted, starts, fed, base) in LAUNCHES.items():
        want = {**base, **ADDS[name, kind]}
        starts, fed = np.asarray(starts, np.int32), np.asarray(fed, np.int32)
        work = launch_work.launch_work(at, kind, form, base["chunk"],
                                       starts, fed)
        assert work == want and list(work) == list(want), (kind, work)
        assert all(type(v) is int for k, v in work.items() if k != "program")
        # with no span open the pairs are not computed; all else stands
        bare = launch_work.launch_work(at, kind, form, base["chunk"],
                                       starts, fed, span=False)
        assert bare == {k: v for k, v in want.items()
                        if not k.startswith("attn_pairs_") or
                        k == "attn_pairs_selected"}
        before = _counters()
        launch_work.count_launch(bare, grouped=form.grouped, lifted=lifted)
        after = _counters()
        grew = {n: after[n] - before.get(n, 0) for n in after
                if n not in before or after[n] != before[n]}
        tally = {"host_calls": 1, f"steps.{kind}": 1}
        if kind == "split":
            tally.update({"split_steps_at.512": 1, "split_lifted_steps": 1,
                          "split_grouped_steps": 1})
        tally.update({RENAMED.get(k, k): v for k, v in want.items()
                      if k not in SPAN_ONLY})
        assert {n: v for n, v in tally.items()
                if v or n not in before} == grew
        # a counter is made even where it stays at 0
        assert set(tally) <= set(after)


@pytest.mark.parametrize("form,fed,every,live", [
    # a grouped instance: its chunk group's 4 rows x 128; the kernel walks
    # the rows of more than one token in whole turns of 64 — 100 -> 128,
    # 128, 37 -> 64 — and the one-token rows are the other group's
    (Form(64, (512, 1024, 2048), 512, 4, 576), (1, 100, 128, 1, 1, 37),
     512, 320),
    # the cell's launch: six whole chunks and a piece of 23 in 8 chunk rows
    (Form(64, (512, 1024, 2048), 1024, 8, 1088),
     (128,) * 6 + (23,) + (1,) * 41, 1024, 832),
    # the row form: EVERY row at the chunk's width, a one-token row a turn
    (Form(8, (), 1024, 8, 1024), (1, 100, 128, 1, 0, 65, 64, 0), 1024, 576),
])
def test_the_delta_rules_chunk_positions(form, fed, every, live, monkeypatch):
    """``delta_chunk_positions`` / ``_live`` at heads of whole lane tiles
    (the published widths), by hand; both the chunk group's whole width
    where the kernels do not run."""
    import dataclasses
    from deepspeed_tpu.ops import ssm
    monkeypatch.setattr(ssm, "DELTA_KERNEL_SUB", 64)
    at = site("qwen3-next-80b-a3b-l12-e64-serve")
    at.model = dataclasses.replace(at.model, ssm_state_size=128,
                                   ssm_head_dim=128)
    layers = at.model.layer_kinds.count(6)
    fed = np.asarray(fed, np.int32)
    for kernels, walked in ((True, live), (False, every)):
        at.use_pallas = kernels
        work = launch_work.launch_work(at, "split", form, 128,
                                       np.zeros_like(fed), fed)
        assert (work["delta_chunk_positions"],
                work["delta_chunk_positions_live"]) == \
            (layers * every, layers * walked)
    assert "delta_chunk_positions" not in launch_work.launch_work(
        at, "decode", Form(8, (), 8, 8, 8), 1, np.zeros_like(fed),
        np.minimum(fed, 1))
    # a stack with no delta-rule layer makes neither
    assert "delta_chunk_positions" not in launch_work.launch_work(
        site("jamba2-3b-l28-serve"), "split", form, 128, np.zeros_like(fed),
        fed)
