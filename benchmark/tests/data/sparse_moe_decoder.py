"""The plain reference for a Mixtral-style decoder: the dense decoder's
block (``dense_decoder.py``: RMSNorm, GQA attention with rotary positions,
untied head, next-token cross-entropy) with the SiLU-GLU replaced by sparse
experts, as HF ``modeling_mixtral.py`` describes them: router logits =
RMSNorm(x) @ router, softmax over ALL experts, the ``num_experts_per_tok``
largest kept and, where ``norm_topk_prob`` says so (Mixtral's code always
does), renormalised to sum to 1; the block's output is the kept weights
times those experts' SiLU-GLU outputs. Training adds a load-balancing
loss weighted by the file's ``router_aux_loss_coef``. Departure: it is
DeepSpeed's (``sharded_moe.py`` ``l_aux``, which the trainer documents),
not HF Mixtral's: experts x sum_e(mean router probability of e x share of
tokens whose FIRST choice is e), means over all tokens of the batch, summed
over the layers.

Plain: every expert is computed for every token and weighted (0 where it
was not chosen); no dispatch, no capacity, no kernel. float32 under
``jax.default_matmul_precision("highest")`` (set by ``dense_decoder``'s
loops). It reads the program's tree layout, ``layers["moe"]``:
``router [D, E]``, ``wg / wi [E, D, F]``, ``wo [E, F, D]``.

A TEST FIXTURE (``tests/test_new_architecture.py`` copies it into a
scratch ``benchmark/reference/``), and the pattern for the file a MoE
configuration brings. It implements the reference contract stated at the
top of ``dense_decoder.py``."""

import dataclasses
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import dense_decoder as dense


@dataclass(frozen=True)
class Widths(dense.Widths):
    experts: int
    per_token: int
    norm_topk: bool
    aux_coef: float

    @classmethod
    def from_hf(cls, hf: dict) -> "Widths":
        base = dataclasses.asdict(dense.Widths.from_hf(hf))
        return cls(**base, experts=int(hf["num_local_experts"]),
                   per_token=int(hf["num_experts_per_tok"]),
                   norm_topk=bool(hf.get("norm_topk_prob", True)),
                   aux_coef=float(hf["router_aux_loss_coef"]))


def matmul_params_per_token(w: Widths) -> int:
    """What one token multiplies: the attention projections, the router,
    the ``per_token`` experts it is routed to (three matrices each), and
    the untied head. The experts it is not routed to do no useful work."""
    qd, kd = w.heads * w.head_dim, w.kv_heads * w.head_dim
    per_layer = w.hidden * qd + 2 * w.hidden * kd + qd * w.hidden \
        + w.hidden * w.experts + w.per_token * 3 * w.hidden * w.ffn
    return w.layers * per_layer + w.hidden * w.vocab


@partial(jax.jit, static_argnames=("w",))
def _layer(x, lp, w: Widths):
    """One block on one sequence, x [T, D] float32 → (x, sum over tokens of
    the router's probabilities [E], first-choice counts [E])."""
    x = dense.attention_block(x, lp, w)
    m = lp["moe"]
    hin = dense._rms_norm(x, lp["ln2"]["scale"], w.eps)
    gates = jax.nn.softmax(hin @ m["router"], axis=-1)          # [T, E]
    topv, topi = jax.lax.top_k(gates, w.per_token)              # [T, k]
    if w.norm_topk:
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    chosen = jax.nn.one_hot(topi, w.experts, dtype=jnp.float32)  # [T, k, E]
    weight = jnp.einsum("tk,tke->te", topv, chosen)

    def expert(args):
        wg, wi, wo, we = args
        return we[:, None] * ((jax.nn.silu(hin @ wg) * (hin @ wi)) @ wo)

    out = jax.lax.map(expert, (m["wg"], m["wi"], m["wo"], weight.T))
    return x + out.sum(0), gates.sum(0), chosen[:, 0].sum(0)


def loss(w: Widths, params, batch: np.ndarray, device) -> float:
    """The trainer's loss on a [B, T] batch: mean next-token cross-entropy
    plus the load-balancing term over all B x T tokens of every layer."""
    routed = []                 # layer-major, one entry a (layer, sequence)

    def layer(x, lp, w_):
        x, gate_sum, first = _layer(x, lp, w_)
        routed.append((gate_sum, first))
        return x

    ce = dense.loss(w, params, batch, device, layer)
    tokens = float(np.asarray(batch).size)
    gate_sum, first = (np.asarray(a, np.float64).reshape(
        w.layers, len(batch), w.experts).sum(1) for a in zip(*routed))
    balance = w.experts * np.sum(gate_sum / tokens * first / tokens)
    return ce + w.aux_coef * float(balance)


def argmax_gaps(w: Widths, params, prompts, outputs, device) -> np.ndarray:
    return dense.argmax_gaps(w, params, prompts, outputs, device,
                             lambda x, lp, w_: _layer(x, lp, w_)[0])
