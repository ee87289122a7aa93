"""A packed split step's attention on two row groups (PR 40): the rows of
more than one token in a chunk group ``[P, c]``, every row as a row of ONE
query ``[n, 1]`` — against the same step with every row at the chunk's
width (``engine_v2._TokenLayout.groups``, ``_split_attention``,
``_at_capacity``)."""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.inference import engine_v2
from deepspeed_tpu.inference.engine_v2 import (RaggedInferenceEngineTPU,
                                               ragged_forward)
from deepspeed_tpu.ops import paged_attention as pa
from deepspeed_tpu.ops import ssm
from deepspeed_tpu.parallel.mesh import build_mesh
from tests.test_paged import _packed_stack

N, C, BS, MB = 8, 16, 8, 8
#: the 8 x 16 program's instances: 32 slots with TWO rows at the chunk's
#: width, 64 slots with all eight
CAPACITIES = (32, 64)
P = 2
#: mix -> (tokens a row feeds, tokens it has cached, does the small
#: instance take it). Histories pass the typed stack's window of 24
MIXES = {
    "no_chunk_row": ([1, 1, 1, 0, 1, 1, 1, 1],
                     [30, 7, 41, 0, 9, 16, 3, 25], True),
    "exactly_p": ([16, 1, 9, 1, 0, 1, 1, 1],
                  [16, 7, 41, 12, 0, 16, 3, 25], True),
    "p_plus_1": ([5, 1, 9, 1, 0, 3, 1, 1],
                 [16, 7, 41, 12, 0, 16, 3, 25], False),
    "fresh_chunk_beside_decode_rows": ([1, 12, 1, 1, 1, 1, 1, 1],
                                       [30, 0, 41, 12, 5, 16, 3, 25], True),
    "rows_without_tokens_first_and_last": ([0, 16, 1, 0, 2, 1, 1, 0],
                                           [0, 8, 41, 0, 33, 16, 3, 0], True),
    # positions 16..31 over 16 cached: the window of 24 ends inside the
    # chunk's own keys for its last queries and inside the history for its
    # first
    "chunk_crosses_the_windows_edge": ([1, 1, 16, 1, 1, 10, 1, 1],
                                       [30, 7, 16, 12, 5, 20, 3, 25], True),
    "tokens_over_the_small_capacity": ([16, 1, 16, 1, 1, 1, 1, 1],
                                       [16, 7, 32, 12, 5, 20, 3, 25], False),
}


def _pages(counts, starts, rng, nb=40, mb=MB, bs=BS):
    """(page table, pages of the arena): the rows' pages out of order; one
    arena size for every mix, so a stack's programs compile once."""
    pages = -(-(starts + counts) // bs)
    assert pages.sum() <= nb and pages.max() <= mb
    pt = np.full((len(counts), mb), nb, np.int32)
    free = iter(rng.permutation(nb))
    for i, n in enumerate(pages):
        pt[i, :n] = [next(free) for _ in range(n)]
    return pt, nb


def _assert_steps_agree(got, want, arena, counts, nb, tol):
    """Two runs of one split step, ``(logits, pools)`` each: the logits of
    every row that fed a token and every pool outside the layers' trash
    pages agree within ``tol``, and the step wrote every pool."""
    (got_logits, got), (want_logits, want) = got, want
    live = counts > 0
    assert np.abs(np.asarray(want_logits)[live]).max() > 0.1
    np.testing.assert_allclose(np.asarray(got_logits)[live],
                               np.asarray(want_logits)[live],
                               rtol=tol, atol=tol)
    assert set(got) == set(want)
    for name in want:
        rows = np.arange(want[name].shape[0])
        # (a state pool's trash is its last slot: a padding row's)
        kept = rows < rows[-1] if ssm.is_state_pool(name) else \
            rows % (nb + 1) != nb
        a, b, before = (np.asarray(x[name], np.float32)[kept]
                        for x in (got, want, arena))
        np.testing.assert_allclose(a, b, rtol=tol, atol=tol, err_msg=name)
        assert not np.array_equal(a, before), name     # the step wrote


#: the 8 x 16 program's LADDER (PR 46): three instances, 16 slots with ONE
#: row at the chunk's width, 32 with two, 64 with all eight
LADDER = (16, 32, 64)
#: mix -> (tokens a row feeds, tokens it has cached, the rung it takes):
#: each rung, and each side of each boundary of tokens and of chunk rows
LADDER_MIXES = {
    "low_rung": ([1, 1, 1, 0, 1, 1, 9, 1],
                 [30, 7, 41, 0, 9, 16, 3, 25], 0),
    "tokens_fill_the_low_rung": ([1, 1, 1, 1, 1, 1, 9, 1],
                                 [30, 7, 41, 12, 9, 16, 0, 25], 0),
    "one_token_over_the_low_rung": ([1, 1, 1, 1, 1, 1, 10, 1],
                                    [30, 7, 41, 12, 9, 16, 3, 25], 1),
    "a_chunk_row_over_the_low_group": ([2, 1, 1, 0, 1, 1, 5, 1],
                                       [30, 7, 41, 0, 9, 16, 3, 25], 1),
    "tokens_fill_the_middle_rung": ([16, 1, 1, 1, 1, 1, 10, 1],
                                    [16, 7, 41, 12, 9, 16, 3, 25], 1),
    "one_token_over_the_middle_rung": ([16, 1, 1, 1, 1, 1, 11, 1],
                                       [16, 7, 41, 12, 9, 16, 0, 25], 2),
    "a_chunk_row_over_the_middle_group": ([5, 1, 9, 1, 0, 3, 1, 1],
                                          [16, 7, 41, 12, 0, 16, 3, 25], 2),
}


def test_instance_rule_is_one_for_the_program_and_the_host():
    """``_at_capacity`` inside a program picks the instance
    ``_instance_index`` names on the host, for every mix: the small one iff
    the tokens fit its slots AND the rows of more than one token fit its
    chunk group."""
    instances = engine_v2._instances(CAPACITIES, N, C)
    assert instances == ((32, P), (64, N))
    assert engine_v2._instances((1024, 2048), 64, 128) == \
        ((1024, 8), (2048, 64))
    assert engine_v2._instances((2048,), 32, 128) == ((2048, 32),)
    ran = jax.jit(lambda counts: engine_v2._at_capacity(
        instances, counts, lambda cap, rows: jnp.int32(cap)))
    for name, (counts, _starts, small) in MIXES.items():
        counts = np.asarray(counts, np.int32)
        index = engine_v2._instance_index(
            instances, int(counts.sum()), int((counts > 1).sum()))
        assert index == (0 if small else 1), name
        assert int(ran(jnp.asarray(counts))) == instances[index][0], name


def test_instance_index_is_the_same_traced_and_on_the_host():
    """``_instance_index`` over the WHOLE (tokens, chunk rows) grid of the 8
    x 16 program's three-rung ladder: traced scalars inside a program give
    the index that ints give on the host, and it is the first rung whose
    slots hold the tokens and whose chunk group holds the chunk rows."""
    instances = engine_v2._instances(LADDER, N, C)
    assert instances == ((16, 1), (32, 2), (64, N))
    assert engine_v2._instances((512, 1024, 2048), 64, 128) == \
        ((512, 4), (1024, 8), (2048, 64))
    traced = jax.jit(lambda t, r: engine_v2._instance_index(instances, t, r))
    seen = set()
    for tokens in range(LADDER[-1] + 1):
        for rows in range(N + 1):
            index = engine_v2._instance_index(instances, tokens, rows)
            assert index == next(
                i for i, (cap, group) in enumerate(instances)
                if tokens <= cap and rows <= group), (tokens, rows)
            assert int(traced(jnp.int32(tokens), jnp.int32(rows))) == index
            seen.add(index)
    assert seen == {0, 1, 2}
    for name, (counts, _starts, rung) in LADDER_MIXES.items():
        counts = np.asarray(counts)
        assert engine_v2._instance_index(
            instances, int(counts.sum()), int((counts > 1).sum())) == rung, \
            name


#: (rows, chunk, kind, ``max_batch_tokens``, ``max_sequences``) ->
#: ``_token_capacities``
_LADDERS = {
    "the_cells_64_rows": ((64, 128, "split", 2048, 64), (512, 1024, 2048)),
    "a_budget_of_16_a_row": ((64, 128, "split", 1024, 64), (512, 1024)),
    "no_room_for_a_chunk_at_8_a_row": ((4, 96, "split", 80, 4), (64, 80)),
    "a_chunk_fits_8_a_row": ((16, 96, "split", 320, 16), (128, 256, 320)),
    "a_chunk_just_fits": ((16, 113, "split", 400, 16), (128, 256, 400)),
    "a_chunk_just_does_not": ((16, 114, "split", 400, 16), (256, 400)),
    "rows_at_twice_the_budget": ((32, 128, "split", 2048, 64), (2048,)),
    "fresh_takes_one": ((64, 128, "fresh", 2048, 64), (2048,)),
    # rows that hold NO MORE than the budget: the ladder under the row
    # slots for the engine's full row count, the row form under it
    "full_rows_hold_the_budget": ((16, 128, "split", 2048, 16),
                                  (512, 1024, 2048)),
    "a_smaller_bucket_holds_the_budget": ((16, 128, "split", 2048, 64), ()),
    "full_rows_under_the_budget": ((16, 96, "split", 2048, 16),
                                   (384, 768, 1536)),
    "eight_full_rows": ((8, 128, "split", 2048, 8), (512, 1024)),
    "four_full_rows": ((4, 128, "split", 2048, 4), ()),
    "a_row_count_between_buckets": ((16, 128, "split", 2048, 12),
                                    (512, 1024, 2048)),
    "fresh_full_rows": ((16, 128, "fresh", 2048, 16), ()),
    "decode": ((64, 1, False, 2048, 64), ()),
}


def _engine_of(top, sequences):
    """What ``_token_capacities`` reads of an engine."""
    import types
    return types.SimpleNamespace(config=types.SimpleNamespace(
        max_batch_tokens=top, max_sequences=sequences))


@pytest.mark.parametrize("case", list(_LADDERS))
def test_token_capacities_make_a_rung_only_where_it_serves(case):
    """``_token_capacities`` from the program's rows, chunk and kind, the
    budget and the engine's row count ALONE: the ladder of the benchmark's
    64 x 128 split program; a rung only under the next one; the rung at 8
    slots a row only where it holds a whole chunk beside one token of every
    other row; under row slots that hold no more than the budget, halves
    for as long as a rung holds four whole chunks, for the engine's full
    row count alone."""
    (nb, cb, fresh, top, sequences), want = _LADDERS[case]
    got = RaggedInferenceEngineTPU._token_capacities(
        _engine_of(top, sequences), nb, cb, fresh)
    assert got == want
    assert all(2 * a <= b or b == top for a, b in zip(got, got[1:]))
    if got and top >= nb * cb:
        assert got[-1] == nb * cb and got[0] >= 4 * cb and len(got) > 1
        assert [cap // cb for cap in got] == \
            [rows for _, rows in engine_v2._instances(got, nb, cb)]
    elif len(got) == 3:
        assert got[0] >= cb + nb - 1
    if len(got) > 1:
        assert engine_v2._write_back_slots(got, nb * cb)[1] % got[0] == 0


#: ``max_sequences`` -> {kind: {rows: ladder}} of an engine at chunk 128
#: under a budget of 2,048 — every program of its grid that is NOT the row
#: form. The 64-sequence engine's (cells 2, 4, 5, 7, 8, 9 and 10) as they
#: stood before the full-row rule (PR 46's tree, every key): that rule
#: reaches the 16- and the 8-sequence engine's full-row split program alone
_GRIDS = {
    64: {"split": {64: (512, 1024, 2048), 32: (2048,)},
         "fresh": {64: (2048,), 32: (2048,)}},
    16: {"split": {16: (512, 1024, 2048)}},
    8: {"split": {8: (512, 1024)}},
    4: {},
}


@pytest.mark.parametrize("sequences,rows,kind", [
    (sequences, rows, kind) for sequences in _GRIDS
    for rows in (1, 2, 4, 8, 16, 32, 64) if rows <= sequences
    for kind in ("split", "fresh", "decode")])
def test_every_program_of_an_engines_grid_holds_its_ladder(sequences, rows,
                                                           kind):
    """``_token_capacities`` of EVERY ``(nb, cb, fresh)`` an engine of
    ``sequences`` rows can be asked for (its row buckets x split / fresh /
    decode) at chunk 128 and a budget of 2,048."""
    cb, fresh = (1, False) if kind == "decode" else (128, kind)
    got = RaggedInferenceEngineTPU._token_capacities(
        _engine_of(2048, sequences), rows, cb, fresh)
    assert got == _GRIDS[sequences].get(kind, {}).get(rows, ())


def _rules_of(top, sequences):
    """The engine's host rules for a launch (``_token_capacities``,
    ``_launch_form``, ``_pick_form``) over what they read of an engine: an
    engine never built, its ``config`` alone."""
    rules = object.__new__(RaggedInferenceEngineTPU)
    rules.config = _engine_of(top, sequences).config
    return rules


#: case -> ((live rows, rows of more than one token, tokens, kind,
#: ``max_sequences``), (the program's rows, its instance's token slots and
#: chunk group)) at chunk 128 under a budget of 2,048
_PICKS = {
    # ISSUE 55's: a 64-sequence engine at an eighth and a quarter of its load
    "8_rows_3_chunks": ((8, 3, 389, "split", 64), (64, 512, 4)),
    "8_rows_4_chunks_fill_the_rung": ((8, 4, 512, "split", 64), (64, 512, 4)),
    "8_rows_4_chunks_and_4_ones_tie": ((8, 4, 516, "split", 64), (8, 1024, 8)),
    "8_rows_5_chunk_rows_tie": ((8, 5, 400, "split", 64), (8, 1024, 8)),
    "16_rows_6_chunks": ((16, 6, 700, "split", 64), (64, 1024, 8)),
    "16_rows_3_chunks": ((16, 3, 397, "split", 64), (64, 512, 4)),
    "16_rows_9_chunks_never_the_top": ((16, 9, 900, "split", 64),
                                       (16, 2048, 16)),
    "16_rows_8_chunks_over_1024": ((16, 8, 1032, "split", 64),
                                   (16, 2048, 16)),
    "32_rows_8_chunks": ((32, 8, 1000, "split", 64), (64, 1024, 8)),
    "32_rows_9_chunks": ((32, 9, 1000, "split", 64), (32, 2048, 32)),
    "between_buckets_5_rows": ((5, 2, 259, "split", 64), (64, 512, 4)),
    "4_rows_tie_at_512": ((4, 2, 258, "split", 64), (4, 512, 4)),
    "2_rows": ((2, 1, 129, "split", 64), (2, 256, 2)),
    "the_full_bucket": ((64, 3, 445, "split", 64), (64, 512, 4)),
    "over_half_the_full_bucket": ((33, 3, 414, "split", 64), (64, 512, 4)),
    "fresh_has_no_ladder": ((8, 3, 384, "fresh", 64), (8, 1024, 8)),
    "decode": ((8, 0, 8, False, 64), (8, 8, 8)),
    # a 16-sequence engine's full-row program (PR 50) takes its 8-row batches
    "16_sequences_8_rows_3_chunks": ((8, 3, 389, "split", 16), (16, 512, 4)),
    "16_sequences_8_rows_5_chunks_tie": ((8, 5, 600, "split", 16),
                                         (8, 1024, 8)),
    "16_sequences_4_rows_tie": ((4, 2, 258, "split", 16), (4, 512, 4)),
    "16_sequences_full": ((16, 6, 700, "split", 16), (16, 1024, 8)),
    # an 8-sequence engine: (512, 1024), and its 4-row batch ties at 512
    "8_sequences_4_rows_tie": ((4, 2, 258, "split", 8), (4, 512, 4)),
    "8_sequences_full": ((8, 3, 389, "split", 8), (8, 512, 4)),
    "4_sequences_no_ladder": ((2, 1, 129, "split", 4), (2, 256, 2)),
}


@pytest.mark.parametrize("case", list(_PICKS))
def test_a_small_split_batch_takes_the_full_row_program_only_for_fewer_slots(
        case):
    """``_pick_form`` from the batch's rows, tokens and chunk rows and the
    engine's chunk, budget and row count ALONE: a split batch under the
    engine's full bucket takes the full-row program iff the instance that
    program picks for it is a GROUPED one with strictly fewer token slots
    than the batch's own program would run and no more attention row slots
    — a tie stays, the top instance is never taken, the full bucket and
    every fresh or decode batch are as they were."""
    (rows, chunk_rows, tokens, kind, sequences), want = _PICKS[case]
    rules = _rules_of(2048, sequences)
    nb, cb = engine_v2._bucket(rows), 128 if kind else 1
    own = rules._launch_form(nb, cb, kind, tokens, chunk_rows)
    form = rules._pick_form(nb, cb, kind, tokens, chunk_rows)
    assert (form.nb, form.slots, form.group_rows) == want
    assert form.capacities == rules._token_capacities(form.nb, cb, kind)
    if form.nb == nb:
        assert form == own
    else:
        assert kind == "split" and \
            form.nb == engine_v2._bucket(sequences) > nb
        assert form.grouped and form.slots < own.slots and \
            form.attn_row_slots == form.group_rows * cb + form.nb <= \
            own.attn_row_slots
    assert tokens <= form.slots and chunk_rows <= form.group_rows


@functools.lru_cache(maxsize=None)
def _stack_steps(stack, dtype):
    """(cfg, params, arena maker, history writer, step(capacities)) of a
    stack in a compute dtype, each program jitted once for all mixes."""
    build_mesh(data=1, devices=jax.devices()[:1])
    cfg, params, make_arena = _packed_stack(stack)
    if make_arena is None:      # the latent stack: one pool
        def make_arena(nb, bs):
            return pa.init_arena_typed(
                cfg.layer_kinds, {2: 1}, nb, bs, cfg.latent_dim, cfg.v_dim,
                jnp.float32)
    if dtype == "bfloat16":
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16)
            if a.dtype == jnp.float32 else a, params)
    # (a recurrent stack: row i's state in slot i of the pools)
    kw = {"slots": jnp.arange(N, dtype=jnp.int32)} if cfg.recurrent else {}
    history = jax.jit(lambda arena, toks, counts, pt: ragged_forward(
        cfg, params, arena, toks, counts, jnp.zeros_like(counts), pt,
        **kw)[1])

    @functools.lru_cache(maxsize=None)
    def step(capacities, slots=None):
        """(``slots``: the rows' state slots where they are not 0..N-1.)"""
        kws = {"slots": jnp.asarray(slots, jnp.int32)} if kw and slots \
            else kw
        return jax.jit(lambda arena, *a: ragged_forward(
            cfg, params, arena, *a, fresh_prefill="split",
            token_capacities=capacities, **kws))
    return cfg, make_arena, history, step


@pytest.mark.parametrize("mix", list(MIXES))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("stack", ["uniform", "typed", "latent"])
def test_grouped_split_step_matches_the_all_rows_instance(devices, stack,
                                                          dtype, mix):
    """A packed split step of a program that holds a grouped instance (32
    slots, 2 chunk rows) and the all-rows one (64 slots), against the same
    step through a program that holds the all-rows instance alone: the
    logits of every row that fed a token, and every pool outside the
    layers' trash pages."""
    cfg, make_arena, history, step = _stack_steps(stack, dtype)
    counts, starts, _small = (np.asarray(a) for a in MIXES[mix])
    counts, starts = counts.astype(np.int32), starts.astype(np.int32)
    rng = np.random.default_rng(len(mix))
    pt, nb = _pages(counts, starts, rng)
    toks = lambda *shape: jnp.asarray(
        rng.integers(0, cfg.vocab_size, shape), jnp.int32)
    arena = make_arena(nb, BS)
    if dtype == "bfloat16":
        arena = {k: v.astype(jnp.bfloat16) for k, v in arena.items()}
    arena = history(arena, toks(N, 48), jnp.asarray(starts), jnp.asarray(pt))
    args = (toks(N, C), jnp.asarray(counts), jnp.asarray(starts),
            jnp.asarray(pt))
    _assert_steps_agree(step(CAPACITIES)(arena, *args),
                        step(CAPACITIES[1:])(arena, *args), arena, counts,
                        nb, 2e-4 if dtype == "float32" else 6e-2)


@pytest.mark.parametrize("mix", list(LADDER_MIXES))
@pytest.mark.parametrize("stack", ["uniform", "typed", "latent", "recurrent"])
def test_three_rung_program_matches_the_row_form(devices, stack, mix):
    """A split step through a program that holds THREE instances of its
    layer loop (16, 32 and 64 slots: one, two and all eight rows at the
    chunk's width) against the same step in the row form, at a batch for
    each rung and on each side of each boundary (tokens = capacity,
    capacity + 1, chunk rows = group + 1): the logits of every row that fed
    a token and every pool outside the trash pages — a recurrent stack's
    state pools too, carried through the one-trip loops."""
    cfg, make_arena, history, step = _stack_steps(stack, "float32")
    counts, starts, rung = LADDER_MIXES[mix]
    counts, starts = (np.asarray(a, np.int32) for a in (counts, starts))
    assert engine_v2._instance_index(
        engine_v2._instances(LADDER, N, C), int(counts.sum()),
        int((counts > 1).sum())) == rung
    rng = np.random.default_rng(len(mix))
    pt, nb = _pages(counts, starts, rng)
    toks = lambda *shape: jnp.asarray(
        rng.integers(0, cfg.vocab_size, shape), jnp.int32)
    arena = history(make_arena(nb, BS), toks(N, 48), jnp.asarray(starts),
                    jnp.asarray(pt))
    args = (toks(N, C), jnp.asarray(counts), jnp.asarray(starts),
            jnp.asarray(pt))
    _assert_steps_agree(step(LADDER)(arena, *args), step(())(arena, *args),
                        arena, counts, nb, 2e-4)


# -- a LIFTED batch (PR 55): few rows, padded to the rows of a program that
# holds a ladder, against the same rows in their own row-form program

def _assert_a_lifted_batch_agrees(lifted, own, arena, pt, launches, shape,
                                  tokens, nb, tol):
    """``launches`` — ``(tokens a row feeds, tokens it has cached)`` of the
    ``len(pt)`` live rows, one launch after the other — through ``lifted``
    (a program of ``shape = (rows, chunk)``: the batch as ``_pack`` pads
    it, zero-count rows on the trash page) and through ``own`` (the live
    rows alone): the live rows' logits and every pool outside the trash
    agree at EVERY launch, each side on the arena its own previous launch
    left — so what a lifted launch wrote is what the next one reads."""
    n, (rows, c) = len(pt), shape

    def padded(a, fill):
        return jnp.asarray(np.concatenate([a, np.full(
            (rows - n,) + a.shape[1:], fill, a.dtype)]))
    arenas = [arena, arena]
    for counts, starts in launches:
        counts, starts = (np.asarray(a, np.int32) for a in (counts, starts))
        toks = np.asarray(tokens(n, c))
        logits, got = lifted(arenas[0], padded(toks, 0), padded(counts, 0),
                             padded(starts, 0), padded(pt, nb))
        want = own(arenas[1], jnp.asarray(toks), jnp.asarray(counts),
                   jnp.asarray(starts), jnp.asarray(pt))
        _assert_steps_agree((logits[:n], got), want, arenas[1], counts, nb,
                            tol)
        arenas = [got, want[1]]


#: mix -> (the launches of FOUR live rows, the rung of the 8 x 16 program's
#: ladder the FIRST takes): a first launch at the low and at the middle rung,
#: then the same rows again — a chunk that goes on, rows of one token over
#: what the first launch wrote
LIFTED_MIXES = {
    "one_chunk_row_then_its_next_chunk": (
        [([9, 1, 1, 1], [16, 7, 41, 12]), ([7, 1, 1, 1], [25, 8, 42, 13])],
        0),
    "two_chunk_rows_then_one_token_rows": (
        [([16, 1, 9, 1], [16, 7, 41, 12]), ([1, 1, 2, 1], [32, 8, 50, 13])],
        1),
}


@pytest.mark.parametrize("mix", list(LIFTED_MIXES))
@pytest.mark.parametrize("stack", ["uniform", "typed", "latent", "recurrent"])
def test_three_rung_program_matches_the_row_form_of_a_lifted_batch(
        devices, stack, mix):
    """A batch of FOUR rows handed to the 8 x 16 program that holds the
    three-rung ladder — padded as ``_pack`` pads it: zero-count rows whose
    pages and state slot are the trash — against the same four rows in
    their own 4 x 16 row-form program, which ``_pick_form`` would have
    taken them from; two launches, the second over the pages and the state
    slots the first wrote. Four stacks: the recurrent one's one-token pass
    then runs over the padding rows' trash slot."""
    cfg, make_arena, history, step = _stack_steps(stack, "float32")
    launches, rung = LIFTED_MIXES[mix]
    n = len(launches[0][0])
    counts, starts = (np.asarray(a, np.int32) for a in launches[0])
    assert engine_v2._instance_index(
        engine_v2._instances(LADDER, N, C), int(counts.sum()),
        int((counts > 1).sum())) == rung
    rng = np.random.default_rng(len(mix))
    held = np.asarray(launches[-1][1]) + np.asarray(launches[-1][0])
    pt, nb = _pages(np.zeros(N, np.int32), np.pad(held, (0, N - n)), rng)
    toks = lambda *shape: jnp.asarray(
        rng.integers(0, cfg.vocab_size, shape), jnp.int32)
    arena = history(make_arena(nb, BS), toks(N, 48),
                    jnp.asarray(np.pad(starts, (0, N - n))), jnp.asarray(pt))
    trash = N       # of the state pools' N + 1 slots
    _assert_a_lifted_batch_agrees(
        step(LADDER, tuple(range(n)) + (trash,) * (N - n)),
        step((), tuple(range(n))), arena, pt[:n], launches, (N, C), toks,
        nb, 2e-4)


# -- the FULL-ROW program of a 16-sequence engine (PR 50): 16 rows of chunk
# 128, the ladder (512, 1024, 2048) under row slots that hold the budget

N16, C16, BS16, MB16, WINDOW16 = 16, 128, 16, 16, 40
LADDER16 = (512, 1024, 2048)
#: mix -> (tokens a row feeds, tokens it has cached, the rung it takes): 16
#: rows of a stack shaped like Command A+'s (a window layer, a full layer
#: with no positional term, 16 queries on one KV head, a parallel block) at
#: each rung and on each side of each boundary. Histories of 30 and 45
#: under chunk rows straddle the window of 40 (a chunk's first queries see
#: the history through it, its last ones their own chunk alone); rows of one
#: token have histories on both sides of it, and row 15 none
_MIXES16 = {
    "two_chunks_beside_fourteen_rows": (
        [128, 72] + [1] * 14,
        [30, 45, 100, 7, 41, 64, 9, 16, 3, 25, 80, 12, 39, 40, 55, 0], 0),
    "tokens_fill_the_low_rung_at_four_chunk_rows": (
        [128, 128, 128, 116] + [1] * 12,
        [30, 0, 45, 96, 100, 7, 41, 64, 9, 16, 3, 25, 80, 12, 39, 0], 0),
    "one_token_over_the_low_rung": (
        [128, 128, 128, 117] + [1] * 12,
        [30, 0, 45, 96, 100, 7, 41, 64, 9, 16, 3, 25, 80, 12, 39, 0], 1),
    "five_chunk_rows": (
        [2, 90, 1, 3, 1, 128, 1, 1, 0, 1, 7, 1, 1, 1, 1, 1],
        [30, 45, 100, 7, 41, 64, 9, 16, 0, 25, 80, 12, 39, 40, 55, 0], 1),
    "tokens_fill_the_middle_rung_at_eight_chunk_rows": (
        [127] * 8 + [1] * 8,
        [30, 45, 0, 7, 41, 64, 9, 16, 3, 25, 80, 12, 39, 40, 55, 0], 1),
    "one_token_over_the_middle_rung": (
        [128, 127, 127, 127, 127, 127, 127, 127] + [1] * 8,
        [30, 45, 0, 7, 41, 64, 9, 16, 3, 25, 80, 12, 39, 40, 55, 0], 2),
    "nine_chunk_rows": (
        [2] * 9 + [1] * 6 + [0],
        [30, 45, 100, 7, 41, 64, 9, 16, 3, 25, 80, 12, 39, 40, 55, 0], 2),
    "every_row_a_whole_chunk": (
        [128] * 16,
        [30, 45, 100, 7, 41, 64, 9, 16, 3, 25, 80, 12, 39, 40, 55, 0], 2),
}


@functools.lru_cache(maxsize=None)
def _steps16():
    """(cfg, arena maker, history writer, step(capacities)) of the small
    parallel-block stack, each program jitted once for all mixes."""
    from deepspeed_tpu.models.hf_loader import config_from_hf
    from deepspeed_tpu.models.transformer import init_params
    from deepspeed_tpu.parallel.moe import held_experts_moe_layer
    from tests.test_cohere2_moe import small
    build_mesh(data=1, devices=jax.devices()[:1])
    cfg = config_from_hf(small(
        num_hidden_layers=2, sliding_window=WINDOW16,
        layer_types=["sliding_attention", "full_attention"]))
    assert cfg.layer_kinds == (1, 0) and cfg.parallel_block and \
        cfg.num_heads // cfg.kv_heads == 16 and not cfg.full_attn_rope
    params = init_params(cfg, jax.random.PRNGKey(5), jnp.float32)

    def make_arena(nb):
        return pa.init_arena_typed(
            cfg.layer_kinds,
            {a: cfg.kind_kv_heads(a) for a in set(cfg.layer_kinds)}, nb,
            BS16, cfg.head_dim, cfg.v_dim, jnp.float32)
    history = jax.jit(lambda arena, toks, counts, pt: ragged_forward(
        cfg, params, arena, toks, counts, jnp.zeros_like(counts), pt,
        moe_fn=held_experts_moe_layer)[1])

    @functools.lru_cache(maxsize=None)
    def step(capacities):
        return jax.jit(lambda arena, *a: ragged_forward(
            cfg, params, arena, *a, moe_fn=held_experts_moe_layer,
            fresh_prefill="split", token_capacities=capacities))
    return cfg, make_arena, history, step


@pytest.mark.parametrize("mix", list(_MIXES16))
def test_full_row_ladder_matches_the_row_form(devices, mix):
    """A 16 x 128 split step through the program a 16-sequence engine now
    holds — instances ``(512, P = 4)``, ``(1024, P = 8)`` and the top at the
    row slots themselves, packed with every row a chunk row — against the
    same step in the row form: the logits of every row that fed a token and
    every pool outside the trash pages."""
    cfg, make_arena, history, step = _steps16()
    counts, starts, rung = _MIXES16[mix]
    counts, starts = (np.asarray(a, np.int32) for a in (counts, starts))
    instances = engine_v2._instances(LADDER16, N16, C16)
    assert instances == ((512, 4), (1024, 8), (2048, 16))
    assert engine_v2._instance_index(
        instances, int(counts.sum()), int((counts > 1).sum())) == rung
    rng = np.random.default_rng(len(mix))
    pt, nb = _pages(counts, starts, rng, N16 * MB16, MB16, BS16)
    toks = lambda *shape: jnp.asarray(
        rng.integers(0, cfg.vocab_size, shape), jnp.int32)
    arena = history(make_arena(nb), toks(N16, 104), jnp.asarray(starts),
                    jnp.asarray(pt))
    args = (toks(N16, C16), jnp.asarray(counts), jnp.asarray(starts),
            jnp.asarray(pt))
    with jax.default_matmul_precision("highest"):
        _assert_steps_agree(step(LADDER16)(arena, *args),
                            step(())(arena, *args), arena, counts, nb, 2e-4)


#: mix -> (the launches of EIGHT live rows, the rung of the 16 x 128
#: program's ladder the first takes): what a 16-sequence engine at half its
#: load hands ``_pick_form`` — the 8 x 128 row form's 1,024 slots for 389
#: tokens, or a tie at 1,024 that the rule leaves alone but the program must
#: still serve. Histories on both sides of the window of 40
_LIFTED16 = {
    "three_chunks_beside_five_rows": (
        [([128, 128, 128, 1, 1, 1, 1, 1], [30, 0, 45, 100, 7, 41, 64, 9]),
         ([128, 72, 1, 1, 1, 1, 1, 1], [158, 128, 173, 101, 8, 42, 65, 10])],
        0),
    "six_chunk_rows": (
        [([128, 90, 128, 40, 2, 128, 1, 1], [30, 0, 45, 100, 7, 41, 64, 9]),
         ([1, 1, 128, 1, 1, 17, 1, 1], [158, 90, 173, 140, 9, 169, 65, 10])],
        1),
}


@pytest.mark.parametrize("mix", list(_LIFTED16))
def test_full_row_ladder_matches_the_row_form_of_a_lifted_batch(devices, mix):
    """EIGHT rows handed to the 16 x 128 program a 16-sequence engine holds
    (padded as ``_pack`` pads them) against the same rows in the 8 x 128
    row-form program of their own bucket, two launches: the windowed
    history call of a chunk group, a parallel block, 16 queries a KV
    head."""
    cfg, make_arena, history, step = _steps16()
    launches, rung = _LIFTED16[mix]
    n = len(launches[0][0])
    counts, starts = (np.asarray(a, np.int32) for a in launches[0])
    assert engine_v2._instance_index(
        engine_v2._instances(LADDER16, N16, C16), int(counts.sum()),
        int((counts > 1).sum())) == rung
    rng = np.random.default_rng(len(mix))
    held = np.asarray(launches[-1][1]) + np.asarray(launches[-1][0])
    pt, nb = _pages(np.zeros(N16, np.int32), np.pad(held, (0, N16 - n)), rng,
                    N16 * MB16, 2 * MB16, BS16)
    toks = lambda *shape: jnp.asarray(
        rng.integers(0, cfg.vocab_size, shape), jnp.int32)
    arena = history(make_arena(nb), toks(N16, 104),
                    jnp.asarray(np.pad(starts, (0, N16 - n))),
                    jnp.asarray(pt))
    with jax.default_matmul_precision("highest"):
        _assert_a_lifted_batch_agrees(
            step(LADDER16), step(()), arena, pt[:n], launches, (N16, C16),
            toks, nb, 2e-4)


#: layer kind -> (query heads, kv heads, K width, V width, window, sink,
#: latent pool (value lanes) or None): what the three stacks hand
#: ``_split_attention``
_KINDS = {"uniform": (4, 2, 16, 16, None, False, None),
          "window_sink": (4, 2, 24, 16, 24, True, None),
          "latent": (4, 4, 24, 16, None, False, 16)}


@pytest.mark.parametrize("mix", [m for m, v in MIXES.items() if v[2]])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", list(_KINDS))
def test_chunk_rows_attention_is_bit_equal_in_a_group(kind, dtype, mix):
    """``_split_attention`` over the grouped layout against the all-rows
    layout at ONE capacity, the same packed q, k, v and pools: a packed
    slot of a chunk row holds the same bits (its row's computation is the
    row form's, in a group of ``P`` rows for ``n``), a slot of a one-token
    row the same number to the rounding of one dot product; a slot past
    the tokens is nobody's."""
    h, kvh, dk, dv, window, has_sink, v_lanes = _KINDS[kind]
    counts, starts, _ = (np.asarray(a, np.int32) for a in MIXES[mix])
    rng = np.random.default_rng(len(mix) + h)
    dt = jnp.dtype(dtype)
    pt, nb = _pages(counts, starts, rng)
    cap, scale = CAPACITIES[0], 0.2
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape), dt)
    qkv = (normal(1, cap, h, dk), normal(1, cap, kvh, dk),
           normal(1, cap, kvh, dv))
    sink = normal(h) if has_sink else None
    kw = {}
    if v_lanes is None:
        pools = (normal(nb + 1, BS, kvh * dk), normal(nb + 1, BS, kvh * dv))
    else:       # absorbed queries over one pool, W_UV after
        pools = (normal(nb + 1, BS, dk), None)
        w_uv = normal(v_lanes, h, dv)
        kw = {"q_history": normal(1, cap, h, dk),
              "expand": lambda o: jnp.einsum("bthl,lhv->bthv", o, w_uv)}

    def history(q, rows):
        return pa.paged_history_with_lse(
            q, *pools, rows.of(jnp.asarray(pt)), rows.of(jnp.asarray(starts)),
            rows.counts, kernel=False, window=window, scale=scale,
            v_lanes=v_lanes)

    def attend(chunk_rows):
        lay = engine_v2._TokenLayout(jnp.asarray(counts),
                                     jnp.asarray(starts), C, cap, chunk_rows)
        assert len(lay.groups()) == (1 if chunk_rows is None else 2)
        return np.asarray(engine_v2._split_attention(
            lay, qkv, history,
            functools.partial(pa.causal_attention_with_lse, window=window,
                              scale=scale),
            scale=scale, sink=sink, **kw)[0], np.float32), lay

    want, lay = attend(None)
    got, _ = attend(P)
    assert got.shape == want.shape == (cap, h, dv)
    row = np.asarray(lay.row[0])
    valid = np.asarray(lay.valid[0])
    wide = (counts > 1)[row] & valid
    assert wide.sum() == counts[counts > 1].sum()
    np.testing.assert_array_equal(got[wide], want[wide])
    one = valid & ~wide
    assert one.sum() == (counts == 1).sum() and np.abs(want[one]).max() > .01
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(got[one], want[one], rtol=tol, atol=tol)


@pytest.mark.parametrize("groups", [4, 16])
@pytest.mark.parametrize("window", [None, 40])
def test_history_kernel_at_one_query_a_row_matches_the_xla_reader(window,
                                                                  groups):
    """The one-token group's history call — ``paged_history_with_lse`` of
    ``[n, 1]`` queries through the kernel (interpret mode), ``window=``
    and all — against ``paged_attention_hist_xla``: live rows within the
    readers' tolerance, a row that rides along (no live query) zeros and
    an lse of -1e30, as a row with no history."""
    rng = np.random.default_rng(groups)
    kvh, bs, mb, nb, dk, dv = 2, 16, 6, 40, 256, 128
    live = np.asarray([1, 1, 0, 1, 1, 0, 1], np.int32)
    starts = np.asarray([50, 90, 33, 64, 17, 48, 0], np.int32)
    n = len(live)
    pt = np.full((n, mb), nb, np.int32)
    free = iter(rng.permutation(nb))                # pages out of order
    for i in range(n):
        for b in range(-(-starts[i] // bs)):
            pt[i, b] = next(free)
    ak = jnp.asarray(rng.standard_normal((nb + 1, bs, kvh * dk)), jnp.float32)
    av = jnp.asarray(rng.standard_normal((nb + 1, bs, kvh * dv)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((n, 1, kvh * groups, dk)),
                    jnp.float32)
    args = (q, ak, av, jnp.asarray(pt), jnp.asarray(starts),
            jnp.asarray(live))
    real = pa.paged_attention_with_lse
    try:
        pa.paged_attention_with_lse = functools.partial(real, interpret=True)
        out, lse = (np.asarray(a) for a in pa.paged_history_with_lse(
            *args, kernel=True, window=window, scale=0.1))
    finally:
        pa.paged_attention_with_lse = real
    out_x, lse_x = (np.asarray(a) for a in pa.paged_history_with_lse(
        *args, kernel=False, window=window, scale=0.1))
    seen = (live > 0) & (starts > 0)
    assert seen.tolist() == [True, True, False, True, True, False, False]
    assert (out[~seen] == 0).all() and (lse[~seen] <= -1e29).all()
    assert (lse_x[-1] <= -1e29).all()
    np.testing.assert_allclose(lse[seen], lse_x[seen], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(out[seen], out_x[seen], rtol=2e-5, atol=2e-5)


#: launch -> (prompts that arrive beside 12 decode rows, the rung the launch
#: takes). 16 rows x chunk 96 over a budget of 320: instances of 128 slots
#: with 1 chunk row, 256 with 2 and 320 with 16
_ENGINE_LAUNCHES = {"one_chunk_row": ((20,), 0),
                    "a_whole_chunk": ((96,), 0),
                    "exactly_p": ((20, 40), 1),
                    "p_plus_1": ((20, 40, 7), 2)}
_ENGINE_INSTANCES = ((128, 1), (256, 2), (320, 16))
_ENGINE = {"dtype": "float32", "max_sequences": 16, "num_blocks": 64,
           "block_size": 8, "max_seq_len": 128, "prefill_chunk": 96}


@pytest.mark.parametrize("launch", list(_ENGINE_LAUNCHES))
def test_engine_counts_the_instance_its_split_launch_took(devices, launch):
    """``dispatch/split_grouped_steps``, ``dispatch/chunk_rows``,
    ``dispatch/attn_row_slots``, ``dispatch/split_steps_at.<slots>`` (and
    ``token_slots``, ``context_slots``' own keys) of a split launch by the
    program's own rule, and the tokens it samples: those of an engine whose
    programs keep the row form."""
    from deepspeed_tpu import telemetry
    build_mesh(data=1, devices=jax.devices()[:1])
    cfg, params, _ = _packed_stack("uniform")
    arrivals, rung = _ENGINE_LAUNCHES[launch]
    slots, group = _ENGINE_INSTANCES[rung]
    grouped = group < 16
    rng = np.random.default_rng(7)
    decoding = [rng.integers(0, cfg.vocab_size, 3).tolist()
                for _ in range(12)]
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in arrivals]
    names = ("split_grouped_steps", "chunk_rows", "attn_row_slots",
             "token_slots", "steps.split", "split_steps_at.128",
             "split_steps_at.256", "split_steps_at.320",
             f"split_steps_at.{16 * 96}")

    def counters():
        return {n: telemetry.registry.counter("dispatch/" + n).value
                for n in names}

    def serve(max_batch_tokens, **engine):
        eng = RaggedInferenceEngineTPU(
            cfg, dict(_ENGINE, max_batch_tokens=max_batch_tokens, **engine),
            params=params)
        uids = list(range(12))
        eng.scheduler.put(uids, decoding)
        out = eng.step_with_budget(budget=320)
        assert eng.last_program == "fresh"
        eng.scheduler.put(uids, [[int(out[u])] for u in uids])
        eng.scheduler.put([12 + i for i in range(len(prompts))], prompts)
        before = counters()
        out = eng.step_with_budget(budget=320)
        assert eng.last_program == "split"
        return eng, {u: int(t) for u, t in out.items()}, \
            {n: v - before[n] for n, v in counters().items() if
             v - before[n]}

    eng, got, grew = serve(320)
    assert eng._token_capacities(16, 96, "split") == (128, 256, 320)
    assert engine_v2._instances((128, 256, 320), 16, 96) == \
        _ENGINE_INSTANCES
    # (16 rows of an engine of 32: no full-row program, the row form)
    _rows, want, row_form = serve(16 * 96, max_sequences=32)
    assert got == want and len(got) == 12 + len(arrivals)
    assert grew == {**({"split_grouped_steps": 1} if grouped else {}),
                    "chunk_rows": len(arrivals),
                    "attn_row_slots": group * 96 + 16 if grouped
                    else 16 * 96,
                    "token_slots": slots, f"split_steps_at.{slots}": 1,
                    "steps.split": 1}
    assert row_form == {"chunk_rows": len(arrivals),
                        "attn_row_slots": 16 * 96, "token_slots": 16 * 96,
                        f"split_steps_at.{16 * 96}": 1, "steps.split": 1}


def test_rung_counters_sum_to_the_split_launches(devices):
    """Over a run of mixed batches — decode rows beside one, two, three
    arrivals or none, under a budget that cuts prompts into chunks — the
    ``dispatch/split_steps_at.<slots>`` counters grow by ``dispatch/
    steps.split`` together, and the split launches' ``dispatch/
    token_slots`` by the sum over the rungs of slots x launches; every rung
    of the ladder is taken."""
    from deepspeed_tpu.telemetry.registry import registry
    build_mesh(data=1, devices=jax.devices()[:1])
    cfg, params, _ = _packed_stack("uniform")
    rng = np.random.default_rng(11)
    eng = RaggedInferenceEngineTPU(
        cfg, dict(_ENGINE, max_batch_tokens=320, num_blocks=128),
        params=params)
    ladder = eng._token_capacities(16, 96, "split")
    assert ladder == (128, 256, 320)

    def read():
        names = ["steps.split", "token_slots", "split_lifted_steps"] + [
            f"split_steps_at.{slots}" for slots in ladder]
        return np.asarray([registry.counter("dispatch/" + n).value
                           for n in names])

    def prompt(n):
        return rng.integers(0, cfg.vocab_size, n).tolist()
    uids = list(range(6))
    eng.scheduler.put(uids, [prompt(3) for _ in uids])
    out = eng.step_with_budget(budget=320)
    split_slots, start, arrivals = 0, read(), iter(
        [(30,), (), (50, 60), (96,), (20, 30, 40), (110, 100), (), (7,)])
    for step in range(12):
        eng.scheduler.put([u for u in uids if u in out],
                          [[int(out[u])] for u in uids if u in out])
        for n in next(arrivals, ()):
            uids.append(len(uids))
            eng.scheduler.put([uids[-1]], [prompt(n)])
        before = read()
        out = eng.step_with_budget(budget=320)
        if eng.last_program == "split":
            split_slots += (read() - before)[1]
    launches, _slots, lifted, *at = read() - start
    assert launches >= 6 and sum(at) == launches and all(at), at
    assert split_slots == sum(slots * n for slots, n in zip(ladder, at))
    # the first launches hold 6-8 rows: they take the 16-row program's low
    # rungs (the 8-row program packs into 320); later ones are full-bucket
    assert 0 < lifted < launches


#: launch -> (decode rows, prompts that arrive beside them, is the FIRST
#: launch lifted — the later ones hold fewer chunk rows as prompts end):
#: a 16-sequence engine (16 x 96 over a budget of 320: instances of 128
#: slots with 1 chunk row, 256 with 2, 320 with 16) under half its load —
#: the 8-row program packs into 320 slots with every row a chunk row
_LIFTED_LAUNCHES = {"one_chunk_row": (5, (200,), True),
                    "two_chunk_rows": (5, (150, 230), True),
                    "three_chunk_rows_stay": (4, (150, 230, 120), False)}


@pytest.mark.parametrize("launch", list(_LIFTED_LAUNCHES))
def test_engine_lifts_a_small_split_batch_and_counts_its_program(devices,
                                                                 launch):
    """An engine at half its rows: its split launches of at most 8 rows take
    the 16-row program's grouped instance where that holds fewer slots
    (``dispatch/split_lifted_steps``; the ``serving/dispatch`` accounting —
    ``token_slots``, ``attn_row_slots``, ``split_steps_at.<slots>``,
    ``kv_write_slots`` — is the program's), over prompts of several chunks
    so that each launch reads the pages the one before wrote; the tokens
    are those of an engine that keeps every batch in its own bucket's
    program."""
    from deepspeed_tpu.telemetry.registry import registry
    build_mesh(data=1, devices=jax.devices()[:1])
    cfg, params, _ = _packed_stack("uniform")
    decoding, arrivals, lifts = _LIFTED_LAUNCHES[launch]
    rng = np.random.default_rng(5)
    first = [rng.integers(0, cfg.vocab_size, 3).tolist()
             for _ in range(decoding)]
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in arrivals]
    names = ("steps.split", "split_lifted_steps", "split_grouped_steps",
             "tokens", "chunk_rows", "token_slots", "attn_row_slots", "kv_write_slots",
             "split_steps_at.128", "split_steps_at.256",
             "split_steps_at.320")

    def serve(own_rows):
        eng = RaggedInferenceEngineTPU(
            cfg, dict(_ENGINE, max_batch_tokens=320, max_seq_len=256,
                      num_blocks=128),
            params=params)
        if own_rows:
            eng._pick_form = eng._launch_form
        uids = list(range(decoding))
        eng.scheduler.put(uids, first)
        out = eng.step_with_budget(budget=320)
        eng.scheduler.put([decoding + i for i in range(len(prompts))],
                          prompts)
        tokens, grew = [], []
        for _ in range(3):      # the prompts' second chunks and on
            eng.scheduler.put(list(out), [[int(t)] for t in out.values()])
            before = [registry.counter("dispatch/" + n).value for n in names]
            out = eng.step_with_budget(budget=320)
            assert eng.last_program == "split"
            tokens.append({u: int(t) for u, t in out.items()})
            grew.append({n: registry.counter("dispatch/" + n).value - b
                         for n, b in zip(names, before)})
        return tokens, grew

    got, grew = serve(False)
    want, own = serve(True)
    assert got == want and all(len(t) >= decoding for t in got)
    assert grew[0]["split_lifted_steps"] == lifts
    for launched, kept in zip(grew, own):
        slots, group = _ENGINE_INSTANCES[engine_v2._instance_index(
            _ENGINE_INSTANCES, launched["tokens"], launched["chunk_rows"])]
        lifts = group < 16      # a grouped instance of the 16-row program
        assert kept["split_lifted_steps"] == 0 and \
            kept["token_slots"] == kept["split_steps_at.320"] * 320 == 320
        assert launched["steps.split"] == 1
        if not lifts:
            assert launched == kept
            continue
        assert launched["split_lifted_steps"] == 1 == \
            launched["split_grouped_steps"] == \
            launched[f"split_steps_at.{slots}"]
        assert launched["token_slots"] == slots < kept["token_slots"]
        assert launched["attn_row_slots"] == group * 96 + 16 <= \
            kept["attn_row_slots"] == 8 * 96
        # the write-back's whole blocks of the lowest rung
        assert launched["kv_write_slots"] % 128 == 0 < \
            launched["kv_write_slots"] <= slots
