"""The indexers' share of their roofline in the traced steps: the (query,
key) pairs those launches must score and the index keys they must read
(``lib/sparse_latent_work``, from the ``index_tokens_scored`` and
``context_tokens`` arguments of the program's ``serving/dispatch`` spans
inside the traced ``serving/engine_step`` spans) through
``flops.roofline_share``, over the device self time, in every ``serve_*``
program on device 0, of the operations under the ``attn_index`` scope — by
scope, so the same work whatever implements it (the projections and the
pool's write ride in that time: the share reads lower for them). A program
without the scope or the argument gives nothing."""

from benchmark.lib import sparse_latent_work

LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SCOPES = ("attn_index",)


def read(run):
    model = run.facts.get("model")
    if run.peaks is None or not getattr(model, "layer_indexer", None):
        return None
    work = sparse_latent_work.traced_work(
        run, ("index_tokens_scored", "context_tokens"))
    seconds = sparse_latent_work.scope_seconds(run, SCOPES)
    if work is None or not seconds:
        return None
    return run.flops.roofline_share(
        sparse_latent_work.score_flops(model, work["index_tokens_scored"]),
        sparse_latent_work.score_bytes(model, work["context_tokens"]),
        seconds, run.peaks)
