"""The picked latent attention's share of its roofline in the traced
steps: the cached rows those launches' queries picked and the softmax over
them (``lib/sparse_latent_work``, from the ``kv_tokens_selected`` and
``attn_pairs_selected`` arguments of the program's ``serving/dispatch``
spans inside the traced ``serving/engine_step`` spans) through
``flops.roofline_share``, over the device self time, in every ``serve_*``
program on device 0, of what computes that softmax: the operations under
the ``attn_history`` and ``attn_core`` scopes (the by-index read of a row
of one query, the masked walk ``mla_decode_picked`` of a chunk's queries,
the chunk's own pairs). A walk that copies pages no query picked, or a
chunk that scores pairs its mask then drops, reads lower for it. A program
without the arguments gives nothing."""

from benchmark.lib import sparse_latent_work

LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SCOPES = ("attn_history", "attn_core")


def read(run):
    model = run.facts.get("model")
    if run.peaks is None or not getattr(model, "layer_indexer", None):
        return None
    work = sparse_latent_work.traced_work(
        run, ("kv_tokens_selected", "attn_pairs_selected"))
    seconds = sparse_latent_work.scope_seconds(run, SCOPES)
    if work is None or not seconds:
        return None
    return run.flops.roofline_share(
        sparse_latent_work.picked_flops(model, work["attn_pairs_selected"]),
        sparse_latent_work.picked_bytes(model, work["kv_tokens_selected"]),
        seconds, run.peaks)
