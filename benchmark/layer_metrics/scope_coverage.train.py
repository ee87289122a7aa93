"""Share of the device's busy time (self times of device 0's operations in
the traced window) whose operation maps to a word of the program's scope
vocabulary, by the program's own table. The guard of every ``*_ms_per_step``
metric read from scopes: it reads low where a table is stale (an executable
from a cache entry older than a scope) or a layer part has no scope."""

from benchmark.trace import scopes

LAYER = "step programs"
MOVES = "train_tokens_per_s_per_chip"


def read(run):
    return scopes.scope_coverage(run)
