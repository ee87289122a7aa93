"""The plain reference against the program's own forward
(``models/transformer.py``) at the ``tiny`` Mistral preset, in float32."""

import numpy as np
import pytest


@pytest.fixture(scope="module")
def tiny():
    import jax
    from deepspeed_tpu.models.mistral import mistral_config
    from deepspeed_tpu.models.transformer import init_params
    from benchmark.reference.dense_decoder import Widths
    cfg = mistral_config("tiny", sliding_window=24)
    params = init_params(cfg, jax.random.PRNGKey(0))
    hf = {"hidden_size": 64, "num_attention_heads": 4,
          "num_key_value_heads": 2, "intermediate_size": 128,
          "vocab_size": 512, "num_hidden_layers": 2, "rms_norm_eps": 1e-5,
          "rope_theta": 10000.0, "sliding_window": 24}
    return cfg, params, Widths.from_hf(hf), jax.devices()[0]


def test_loss_matches_the_programs_forward(tiny):
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import transformer
    from benchmark.reference import dense_decoder
    cfg, params, widths, dev = tiny
    toks = np.random.default_rng(0).integers(0, 512, (3, 64), dtype=np.int32)
    ours = dense_decoder.loss(widths, params, toks, dev)
    with jax.default_matmul_precision("highest"):
        logits = transformer.forward(cfg, params, jnp.asarray(toks))
    lp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    theirs = -float(jnp.take_along_axis(
        lp, jnp.asarray(toks[:, 1:])[..., None], axis=-1).mean())
    # float32 both sides; the window (24 < 64) bites, so the mask is tested
    assert ours == pytest.approx(theirs, abs=2e-5)


def test_argmax_gaps_are_zero_for_the_references_own_greedy_tokens(tiny):
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import transformer
    from benchmark.reference import dense_decoder
    cfg, params, widths, dev = tiny
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 512, n).tolist() for n in (5, 17)]
    outs = []
    for p in prompts:                  # greedy, by the program's forward
        seq = list(p)
        for _ in range(6):
            with jax.default_matmul_precision("highest"):
                logits = transformer.forward(
                    cfg, params, jnp.asarray([seq], jnp.int32))
            seq.append(int(jnp.argmax(logits[0, -1])))
        outs.append(seq[len(p):])
    gaps = dense_decoder.argmax_gaps(widths, params, prompts, outs, dev)
    assert gaps.shape == (12,) and float(gaps.max()) <= 1e-4
    # a wrong token is scored below the argmax
    outs[0][3] = (outs[0][3] + 1) % 512
    bad = dense_decoder.argmax_gaps(widths, params, prompts, outs, dev)
    assert bad[3] > 0
