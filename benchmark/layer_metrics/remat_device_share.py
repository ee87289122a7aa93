"""Share of the device's busy time spent computing forward operations AGAIN
for the backward pass: self time of the operations whose ``op_name`` holds
``rematted_computation`` (``jax.checkpoint``: the layer loop's remat policy
and the chunked CE) over the self time of all operations of device 0 in the
traced window (``trace/scopes.py``)."""

from benchmark.trace import scopes

LAYER = "step programs"
MOVES = "train_tokens_per_s_per_chip"


def read(run):
    dev = scopes.analysis(run)["device"]
    if dev is None or not dev["scoped_ns"]:
        return None
    return 100.0 * dev["remat_ns"] / dev["sum_ns"]
